"""Model FLOPs utilisation: forward+backward model FLOPs per token (the
architecture module's ``train_flops_per_token``) times ``tokens_per_s``,
over chips times the bf16 peak."""


def read(run):
    rate = len(run["steps"]) * run["tokens_per_step"] / run["window_s"]
    return 100.0 * rate * run["flops_per_token"] / (
        run["chips"] * run["peak"]["bf16_flops"])
