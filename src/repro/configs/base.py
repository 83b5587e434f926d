"""Config system: model architecture, optimizer, input shapes, run configs.

Every assigned architecture gets a ``ModelConfig`` in its own module citing
its source. Shapes are the four assigned global input shapes. ``RunConfig``
composes model x shape x mesh x optimizer for the launcher/dry-run.
"""
from __future__ import annotations

import dataclasses

from repro.comm.faults import FaultConfig
from repro.comm.gossip import GossipConfig
from repro.comm.overlap import OverlapConfig
from repro.core.armijo import ArmijoConfig
from repro.core.compression import Compressor
from repro.core.gamma import GammaControllerConfig


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                   # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0             # 0 -> d_model // n_heads
    qkv_bias: bool = False
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    # --- MoE ---
    n_experts: int = 0
    experts_per_token: int = 0
    moe_d_ff: int = 0             # per-expert hidden size
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    # beyond-paper perf: explicit expert-parallel shard_map (each model
    # shard routes+computes its local experts on its replicated token set;
    # one psum combines) instead of auto-partitioned gathers — see §Perf.
    moe_expert_parallel: bool = False
    # --- SSM (Mamba2) ---
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv: int = 4
    ssm_chunk: int = 256
    # --- hybrid (zamba2) ---
    shared_attn_every: int = 0    # >0: tied attn block every k ssm layers
    # --- enc-dec ---
    n_enc_layers: int = 0
    n_dec_layers: int = 0
    # --- VLM ---
    cross_attn_every: int = 0     # >0: one cross-attn layer per k self layers
    n_patches: int = 0
    # --- rwkv ---
    rwkv_lora_rank: int = 64
    # --- attention variants ---
    sliding_window: int = 0       # 0 = full attention
    swa_for_long_context: bool = True  # long_500k uses window if full-attn
    long_context_window: int = 8192
    # --- numerics / impl ---
    seq_parallel: bool = False    # Megatron-SP residual stream (S over model)
    # beyond-paper: int8 self-attention KV cache (per-position absmax
    # scales) — halves the decode shapes' dominant HBM term vs bf16.
    kv_cache_dtype: str = ""      # "" = compute dtype | "int8"
    param_dtype: str = "float32"
    compute_dtype: str = "float32"
    attn_chunk: int = 1024        # query-chunked attention above this seq len
    remat: bool = True
    citation: str = ""

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def padded_vocab(self) -> int:
        """Vocab rounded up to a 256 multiple so embedding/head tables shard
        over any model-axis size; padded logits are masked in lm_head."""
        return -(-self.vocab_size // 256) * 256

    @property
    def attn_free(self) -> bool:
        return self.family == "ssm" and self.shared_attn_every == 0

    @property
    def subquadratic(self) -> bool:
        return self.family in ("ssm", "hybrid") or self.sliding_window > 0

    def n_params(self) -> int:
        """Analytic parameter count (for 6ND model-FLOPs and memory checks)."""
        D, V = self.d_model, self.vocab_size
        hd = self.hd
        emb = V * D * (1 if self.tie_embeddings else 2)

        def attn_p():
            qp = self.n_heads * hd * D
            kvp = 2 * self.n_kv_heads * hd * D
            op = self.n_heads * hd * D
            b = (self.n_heads + 2 * self.n_kv_heads) * hd if self.qkv_bias else 0
            return qp + kvp + op + b

        def mlp_p(ff):
            return 3 * D * ff            # SwiGLU gate+up+down

        def mamba_p():
            d_in = self.ssm_expand * D
            nh = d_in // self.ssm_head_dim
            in_proj = D * (2 * d_in + 2 * self.ssm_state + nh)
            conv = (d_in + 2 * self.ssm_state) * self.ssm_conv
            out = d_in * D
            return in_proj + conv + out + 2 * nh + nh  # A, D, dt_bias

        def rwkv_p():
            tm = 4 * D * D + D * D       # r,k,v,g + output
            w_lora = 2 * D * self.rwkv_lora_rank * 5
            cm = 2 * D * self.d_ff       # channel mix
            return tm + w_lora + cm + 6 * D

        per_layer = 0
        n_layers = self.n_layers
        if self.family in ("dense", "vlm"):
            per_layer = attn_p() + mlp_p(self.d_ff) + 2 * D
        elif self.family == "moe":
            per_layer = attn_p() + 2 * D + \
                self.n_experts * 3 * D * self.moe_d_ff + D * self.n_experts
        elif self.family == "ssm" and self.shared_attn_every == 0:
            per_layer = rwkv_p() + 2 * D if self.name.startswith("rwkv") \
                else mamba_p() + 2 * D
        elif self.family == "hybrid":
            per_layer = mamba_p() + 2 * D
        elif self.family == "encdec":
            enc = attn_p() + mlp_p(self.d_ff) + 2 * D
            dec = 2 * attn_p() + mlp_p(self.d_ff) + 3 * D
            return emb + self.n_enc_layers * enc + self.n_dec_layers * dec + D
        total = emb + n_layers * per_layer + D
        if self.family == "vlm" and self.cross_attn_every:
            n_cross = self.n_layers // self.cross_attn_every
            total += n_cross * (2 * attn_p() + 2 * D)
        if self.family == "hybrid" and self.shared_attn_every:
            total += attn_p() + mlp_p(self.d_ff) + 2 * D  # one tied block
        return int(total)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                 # train | prefill | decode


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class FederatedConfig:
    """Federated cohort simulation (DESIGN.md §13, repro/fed/).

    ``n_clients`` > 0 turns the train step into a cohort round: each dp
    worker ``vmap``s ``n_clients / W`` simulated clients (per-client EF
    memory, gamma controller, and Armijo step in ``DistOptState.fed``)
    through the compressed exchange, ONE all_gather + ONE psum for the
    whole cohort.  Client participation is sampled host-side per round
    (repro/fed/sampling.py) and enters the batch as a replicated
    ``"participation"`` mask.
    """

    n_clients: int = 0            # 0 = disabled (plain dp training)
    clients_per_round: int = 0    # fixed sampler: 0 -> all clients
    sampling: str = "fixed"       # fixed | bernoulli
    participation_rate: float = 1.0   # bernoulli per-client probability
    straggler_rate: float = 0.0   # drop each selected client with this p
    # "support" divides each coordinate by its nonzero-support count
    # across participants (fed_dropout_avg-style — fixes the dense mean
    # averaging zeros into unsent coordinates); "mean" keeps the
    # zero-averaging dense mean as the reference (repro/fed/aggregate.py)
    aggregation: str = "support"
    # per-client gamma controllers (fixed | linear schedules; the linear
    # ramp advances on each client's OWN participation counter, so
    # clients genuinely carry heterogeneous k_t)
    per_client_gamma: bool = True
    dirichlet_alpha: float = 0.0  # >0: non-IID client data skew
    seed: int = 0                 # sampling stream seed

    @property
    def enabled(self) -> bool:
        return self.n_clients > 0

    def __post_init__(self):
        from repro.fed.aggregate import validate_aggregation
        from repro.fed.sampling import validate_sampler
        validate_sampler(self.sampling)
        validate_aggregation(self.aggregation)
        if self.n_clients < 0:
            raise ValueError(f"n_clients must be >= 0, got {self.n_clients}")
        if not 0 <= self.clients_per_round <= self.n_clients:
            raise ValueError(
                f"clients_per_round={self.clients_per_round} out of range "
                f"for n_clients={self.n_clients}")
        if not 0.0 <= self.participation_rate <= 1.0:
            raise ValueError(f"participation_rate must be in [0, 1], got "
                             f"{self.participation_rate}")
        if not 0.0 <= self.straggler_rate < 1.0:
            raise ValueError(f"straggler_rate must be in [0, 1), got "
                             f"{self.straggler_rate}")


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    kind: str = "csgd_asss"   # csgd_asss | nonadaptive | acgd | sgd | sls | dense
    armijo: ArmijoConfig = ArmijoConfig()
    compressor: Compressor = Compressor()
    # per-round compression-level controller (AdaCGD-style adaptive gamma;
    # repro/core/gamma.py + DESIGN.md §9/§10) — takes effect when
    # ``compressor.max_gamma`` > 0 sizes the ragged wire budget.  The
    # ``ef-coupled`` schedule closes the armijo-coupled observability gap
    # by coupling to the per-worker CompressionTelemetry (EF backlog /
    # decode cosine) that the train step threads through DistOptState.
    gamma_controller: GammaControllerConfig = GammaControllerConfig()
    eta: float = 0.1              # for non-adaptive baselines + acgd
    momentum: float = 0.9         # acgd: Nesterov mu (arXiv 2002.11364)
    ef_dtype: str = "float32"
    ef_host_offload: bool = False  # beyond-paper: EF memory in host RAM
    # beyond-paper: compress per (layer, model-shard) under a nested
    # manual-model shard_map so top_k never gathers the full gradient
    # (same contraction constant — see DESIGN.md §3; §Perf iteration 1).
    shard_local_topk: bool = False
    # beyond-paper (paper §V lists local iterations as future work):
    # Qsparse-local-style — each worker takes `local_steps` uncompressed
    # Armijo-SGD steps on its own microbatches, then the accumulated model
    # delta is EF-compressed and exchanged once.  Divides exchange
    # frequency by local_steps.  Requires microbatches == local_steps.
    local_steps: int = 1
    # transport schedule of the compressed exchange, validated against
    # the repro.comm.transport registry — the ONE source of truth for
    # valid names (DESIGN.md §11/§12): "bucketed" coalesces every leaf
    # into ONE flat packed all_gather + batched kernel launches + ONE
    # dense pmean; "perleaf" is the bit-exact reference schedule (one
    # collective per leaf) kept for parity tests and paired benchmarks;
    # "gossip" is the serverless neighbor-ppermute exchange; "overlap"
    # streams the bucket buffer over a chunked ppermute ring and ships
    # the previous step's payload so the collective hides behind compute
    # (DESIGN.md §14).
    transport: str = "bucketed"
    # gossip/consensus hyper-parameters; only read when transport="gossip"
    gossip: GossipConfig = GossipConfig()
    # overlap ring/staleness knobs; only read when transport="overlap"
    overlap: OverlapConfig = OverlapConfig()
    # federated cohort simulation (DESIGN.md §13): n_clients > 0 vmaps a
    # client cohort above the dp mesh with per-client EF/gamma state and
    # support-weighted aggregation of the decoded top-k payloads
    federated: FederatedConfig = FederatedConfig()
    # downlink direction (DESIGN.md §15): "dense" returns the decoded
    # aggregate as the full f32 mean (bit-exact reference, charged dense
    # bytes per link); "compressed" re-compresses the replicated aggregate
    # through the SAME WireSpec geometry with a server-side EF memory
    # (repro/comm/downlink.py) — no extra collective, the §11 schedule
    # stays ONE all_gather + ONE pmean.
    downlink: str = "dense"
    # ragged §9 valid counts of the downlink payload; fixed | linear only
    # (the server has no Armijo search and no per-worker EF telemetry to
    # couple to)
    downlink_gamma: GammaControllerConfig = GammaControllerConfig()
    # hostile-wire robustness (DESIGN.md §16): seeded fault-injection
    # campaign applied at the gathered-payload boundary.  All rates 0.0
    # (the default) means no injection; the defensive decode verdicts and
    # the step-level circuit breaker stay armed either way.
    faults: FaultConfig = FaultConfig()
    # circuit breaker: a non-finite round (loss or decoded update) skips
    # the parameter write with all carried optimizer state bit-frozen;
    # this many CONSECUTIVE skips raise DivergenceError on the host
    # (repro/core/health.py).  0 disables the gate (legacy behavior:
    # non-finite rounds write through).
    max_consecutive_skips: int = 25

    def __post_init__(self):
        from repro.comm.transport import validate_transport
        validate_transport(self.transport)
        from repro.comm.downlink import MODES as DOWNLINK_MODES
        if self.downlink not in DOWNLINK_MODES:
            raise ValueError(f"unknown downlink mode {self.downlink!r} "
                             f"(want one of {DOWNLINK_MODES})")
        if self.downlink == "compressed":
            if self.downlink_gamma.schedule not in ("fixed", "linear"):
                raise ValueError(
                    "downlink_gamma supports only the open-loop fixed | "
                    "linear schedules — the simulated server has no Armijo "
                    "search or per-worker EF telemetry to couple to "
                    f"(got {self.downlink_gamma.schedule!r})")
            if self.transport in ("gossip", "overlap"):
                raise ValueError(
                    "downlink='compressed' re-compresses a replicated "
                    "global aggregate; transport="
                    f"{self.transport!r} never materializes one "
                    "(gossip mixes neighbors, overlap applies stale "
                    "payloads — DESIGN.md §12/§14/§15)")
            if self.federated.enabled:
                raise ValueError(
                    "downlink='compressed' does not compose with the "
                    "federated cohort yet — the cohort's support-weighted "
                    "aggregate is produced inside the fed worker "
                    "(DESIGN.md §13), not by the §11 transport the "
                    "downlink hooks")
        if self.federated.enabled and self.transport == "gossip":
            raise ValueError(
                "federated cohort simulation does not compose with "
                "transport='gossip' — the cohort has its own one-gather "
                "collective schedule (DESIGN.md §13)")
        if self.federated.enabled and self.transport == "overlap":
            raise ValueError(
                "federated cohort simulation does not compose with "
                "transport='overlap' — the cohort gather carries per-client "
                "rows on its own schedule (DESIGN.md §13/§14)")
        if self.max_consecutive_skips < 0:
            raise ValueError(
                f"max_consecutive_skips must be >= 0 (0 disables the "
                f"breaker), got {self.max_consecutive_skips}")
        if self.faults.enabled:
            if self.kind not in ("csgd_asss", "nonadaptive", "acgd"):
                raise ValueError(
                    f"fault injection corrupts the packed uplink wire "
                    f"(DESIGN.md §16); kind={self.kind!r} ships a dense "
                    f"pmean with no wire to corrupt — use csgd_asss | "
                    f"nonadaptive | acgd")
            if self.downlink == "compressed":
                raise ValueError(
                    "fault injection does not compose with "
                    "downlink='compressed' — the 'faulty' wrapper is a "
                    "stateful transport and the downlink hook requires a "
                    "stateless one (DESIGN.md §15/§16)")
            if self.shard_local_topk:
                raise ValueError(
                    "fault injection does not compose with "
                    "shard_local_topk — fault sites are keyed by whole-"
                    "gradient leaf index, not a model shard's lane set "
                    "(DESIGN.md §16)")


@dataclasses.dataclass(frozen=True)
class RunConfig:
    model: ModelConfig
    shape: ShapeConfig
    optimizer: OptimizerConfig = OptimizerConfig()
    multi_pod: bool = False
    microbatches: int = 1          # gradient accumulation per worker
    seq_shard_activations: bool = True   # sequence-parallel residual stream


def smoke_variant(cfg: ModelConfig) -> ModelConfig:
    """Reduced same-family config for CPU smoke tests (prompt contract:
    2 layers, d_model <= 512, <= 4 experts)."""
    kw = dict(
        n_layers=2, d_model=128, n_heads=4,
        n_kv_heads=min(cfg.n_kv_heads, 4) if cfg.n_kv_heads else 0,
        d_ff=256, vocab_size=512, head_dim=32,
        param_dtype="float32", compute_dtype="float32",
        attn_chunk=64, remat=False,
    )
    if cfg.family == "moe":
        # capacity_factor = E/k so C = T (drop-free): smoke tests assert
        # exact decode/forward consistency, which dropping would break.
        kw.update(n_experts=4, experts_per_token=2, moe_d_ff=64,
                  capacity_factor=2.0)
    if cfg.family in ("ssm", "hybrid"):
        kw.update(ssm_state=16, ssm_head_dim=32)
    if cfg.family == "hybrid":
        kw.update(n_layers=5, shared_attn_every=2)
    if cfg.family == "encdec":
        kw.update(n_enc_layers=2, n_dec_layers=2)
    if cfg.family == "vlm":
        kw.update(n_layers=4, cross_attn_every=2, n_patches=16)
    if cfg.name.startswith("rwkv"):
        kw.update(rwkv_lora_rank=8)
    if cfg.sliding_window:
        kw.update(sliding_window=32)
    return dataclasses.replace(cfg, **kw)
