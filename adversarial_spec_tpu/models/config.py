"""Model-family configuration.

One generic decoder-only transformer (models/transformer.py) covers every
opponent family the debate targets — Llama-3, Mistral, Gemma-2, Qwen-2 —
via config flags, instead of one module per family. The families differ
only in: GQA ratio, activation, RoPE theta, norm placement (Gemma-2's
sandwich norms), attention/final logit softcapping (Gemma-2), sliding-window
attention (Mistral, alternating layers in Gemma-2), QKV bias (Qwen-2),
embedding scaling and tied embeddings (Gemma-2).

Replaces (reference): the per-provider model zoo behind litellm
(scripts/providers.py:18-43) — here a model is a shape, not an API endpoint.
"""

from __future__ import annotations

from dataclasses import dataclass, replace

# Width of one vector register row on the TPU: a paged pool whose minor
# dimension is a multiple of it can be cut page by page by a kernel
# (ops/pallas_paged.py ``_sliceable``).
LANES = 128


@dataclass(frozen=True)
class YarnRope:
    """YaRN rope scaling (HF ``rope_type="yarn"``) plus the position
    scaling of queries (``llama_4_scaling_beta``)."""

    factor: float
    original_max: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0
    query_scaling_beta: float = 0.0  # q *= 1 + beta * ln(1 + pos // original_max)


@dataclass(frozen=True)
class LatentAttention:
    """Multi-head latent attention: queries through a low-rank
    bottleneck, keys and values expanded from ONE compressed vector a
    token (``kv_rank`` wide) plus one rotated key shared by all heads
    (``rope_dim`` wide). The cache holds those two and nothing per head."""

    q_rank: int
    kv_rank: int
    nope_dim: int  # per head, not rotated
    rope_dim: int  # per head for queries; the one shared key
    v_dim: int  # per head
    yarn: YarnRope | None = None

    @property
    def rope_pad(self) -> int:
        """Cached width of the shared rotated key: ``rope_dim`` padded
        with zeros up to whole lanes, so that a kernel can cut its pages."""
        return -(-self.rope_dim // LANES) * LANES

    @property
    def softmax_scale(self) -> float:
        """1/sqrt(qk head dim), times YaRN's mscale squared where the
        config gives ``mscale_all_dim`` (the DeepSeek-V2 convention the
        key comes from)."""
        import math

        scale = 1.0 / math.sqrt(self.nope_dim + self.rope_dim)
        y = self.yarn
        if y is not None and y.mscale_all_dim and y.factor > 1.0:
            m = 0.1 * y.mscale_all_dim * math.log(y.factor) + 1.0
            scale *= m * m
        return scale


@dataclass(frozen=True)
class RoutedExperts:
    """A routed FFN: a softmax router over ALL ``n_routed`` experts picks
    ``top_k`` a token; this chip holds ``held`` = (first, count) of them
    (its share of an expert-parallel deployment) and computes their part
    of the result; ``n_shared`` always-on experts of the same width are
    computed whole beside them."""

    n_routed: int
    top_k: int
    expert_dim: int
    n_shared: int = 0
    norm_topk: bool = True
    routed_scaling: float = 1.0
    held: tuple[int, int] = (0, 0)  # (0, 0) = all of them
    # "sigmoid": scores are sigmoids, the top k are chosen by score + a
    # bias an expert (``router_bias``, learned for load balance), and the
    # weights come from the scores alone (models/moe.py ``route``).
    scoring: str = "softmax"  # softmax | sigmoid
    # deviation of the synthetic checkpoint's ``router_bias`` (a trained
    # one loads its own); it has to move the choice or a program that
    # drops the bias serves the same tokens
    bias_std: float = 0.0

    @property
    def first_held(self) -> int:
        return self.held[0]

    @property
    def n_held(self) -> int:
        return self.held[1] or self.n_routed


@dataclass(frozen=True)
class StateSpace:
    """A Mamba-2 mixer: ``n_heads`` heads of ``head_dim`` channels
    (together the inner width), each with a [head_dim, state_dim]
    recurrent state; B and C are shared by the heads of a group; a causal
    depthwise conv of ``conv_width`` taps over x, B and C precedes the
    recurrence. What a sequence keeps of such a layer is its state and
    the conv's last ``conv_width - 1`` inputs, not keys and values."""

    n_heads: int
    head_dim: int
    state_dim: int
    n_groups: int = 1
    conv_width: int = 4
    chunk: int = 256  # positions a step of the chunked scan covers

    @property
    def inner_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the conv runs over: x, then B and C of every group."""
        return self.inner_dim + 2 * self.n_groups * self.state_dim

    @property
    def in_dim(self) -> int:
        """Columns of the input projection: gate z, conv channels, dt."""
        return self.inner_dim + self.conv_dim + self.n_heads


@dataclass(frozen=True)
class GatedAttention:
    """What afmoe's attention adds to GQA: an RMSNorm over each head of
    the queries and of the keys (a learned weight ``head_dim`` wide,
    before any rotation), and a sigmoid gate ``sigmoid(h W_g)`` on the
    heads' output before ``W_o``. Its layers are of two kinds, which a
    period's positions name as their mixer: "swa" rotates queries and
    keys and sees the last ``window`` positions (its own included);
    "nope" rotates nothing and sees every earlier position."""

    window: int


@dataclass(frozen=True)
class DensePrefix:
    """Layers ahead of the first period: each with the attention kind
    ``mixers`` names, each with a dense FFN ``ffn_dim`` wide, whatever
    the periods' FFN is. They have a weight stack of their own
    (``params["leading"]``) and the first rows of the KV cache."""

    mixers: tuple[str, ...]
    ffn_dim: int


# Mixer kinds that are grouped-query attention over cached keys and
# values: one weight stack, one pool, whatever each layer rotates or sees.
GQA_MIXERS = ("gqa", "swa", "nope")


@dataclass(frozen=True)
class ModelConfig:
    """Architecture hyperparameters for one decoder-only transformer."""

    vocab_size: int = 32000
    dim: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 8
    head_dim: int = 64
    ffn_dim: int = 1376
    rope_theta: float = 500000.0
    rms_eps: float = 1e-5
    activation: str = "silu"  # silu | gelu
    tied_embeddings: bool = False
    # Gemma-2 extras.
    scale_embeddings: bool = False  # multiply embeddings by sqrt(dim)
    post_norms: bool = False  # post-attention/post-ffn sandwich norms
    logit_softcap: float = 0.0  # final-logit soft capping (30.0 in gemma-2)
    attn_softcap: float = 0.0  # attention-logit soft capping (50.0)
    # Sliding-window attention: 0 = global everywhere. When
    # ``sliding_window_pattern`` is 2 (gemma-2), odd layers are global and
    # even layers use the window; pattern 1 (mistral) windows every layer.
    sliding_window: int = 0
    sliding_window_pattern: int = 1
    qkv_bias: bool = False  # qwen-2
    # Llama-3.1/3.2 rope scaling (HF rope_type="llama3"): 0 = unscaled.
    rope_scaling_factor: float = 0.0
    rope_low_freq_factor: float = 1.0
    rope_high_freq_factor: float = 4.0
    rope_original_max: int = 8192
    max_seq_len: int = 8192
    norm_scale_plus_one: bool = False  # gemma RMSNorm uses (1 + weight)
    # Gemma-2 "query_pre_attn_scalar": attention scale is 1/sqrt(this)
    # instead of 1/sqrt(head_dim). 0 = use head_dim (all other families;
    # gemma-2-9b's value equals its head_dim, 27b's does NOT: 4608/32=144).
    query_pre_attn_scalar: float = 0.0
    # Granite's multipliers (1 = what every other family computes) and
    # its "nope" position embedding (``rope`` False: nothing is rotated).
    embedding_multiplier: float = 1.0  # x = E[token] * this
    residual_multiplier: float = 1.0  # x = x + this * block(norm(x))
    attention_multiplier: float = 0.0  # the softmax scale itself; 0 = unset
    logits_scaling: float = 1.0  # logits / this
    rope: bool = True
    # One period of the layer pattern, as (mixer kind, FFN kind): mixer
    # "gqa" | "swa" | "nope" | "latent" | "ssm", FFN "dense" | "routed".
    # A kind's sizes live in its own group below, not as more flags on
    # this set. A period holds attention of one shape at most (the
    # ``GQA_MIXERS`` share one), and one kind of FFN. ``prefix`` layers
    # stand ahead of the first period; the periods then repeat to
    # ``n_layers``: whole ones where a period holds more than one weight
    # stack (state-space layers: the forwards scan such a stack period by
    # period), else layer by layer, so that the last may be cut short
    # (Trinity's 54 routed layers are thirteen periods and a half).
    layer_kinds: tuple[tuple[str, str], ...] = (("gqa", "dense"),)
    latent: LatentAttention | None = None
    experts: RoutedExperts | None = None
    ssm: StateSpace | None = None
    gated: GatedAttention | None = None
    prefix: DensePrefix | None = None

    def __post_init__(self):
        for kinds in self.layer_kinds:
            if kinds[0] not in (*GQA_MIXERS, "latent", "ssm") or kinds[
                1
            ] not in ("dense", "routed"):
                raise ValueError(f"unknown layer kinds {kinds}")
        mixers = {m for m, _ in self.layer_kinds}
        ffns = {f for _, f in self.layer_kinds}
        shapes = {"gqa" if m in GQA_MIXERS else m for m in mixers}
        if len(shapes - {"ssm"}) > 1 or len(ffns) != 1:
            raise NotImplementedError(
                f"one kind of attention and one of FFN a period, not "
                f"{self.layer_kinds}"
            )
        if self.ssm is not None and (self.n_layers - self.n_leading) % self.period:
            raise ValueError(
                f"{self.n_layers} layers are no whole number of periods "
                f"of {len(self.layer_kinds)}"
            )
        if (
            ("latent" in mixers) != (self.latent is not None)
            or ("ssm" in mixers) != (self.ssm is not None)
            or ("routed" in ffns) != (self.experts is not None)
            or bool(mixers & {"swa", "nope"}) != (self.gated is not None)
        ):
            raise ValueError(
                f"layer kinds {self.layer_kinds} and the latent/ssm/experts/"
                "gated groups disagree"
            )
        if self.ssm is not None and (
            self.latent is not None or self.experts is not None
        ):
            raise NotImplementedError(
                "state-space layers stand beside GQA attention and a dense FFN"
            )
        if self.prefix is not None and (
            not set(self.prefix.mixers) <= mixers & set(GQA_MIXERS)
            or not 0 < self.n_leading <= self.n_layers
        ):
            raise NotImplementedError(
                f"leading layers {self.prefix.mixers} are of the periods' "
                f"kinds of attention ({sorted(mixers)}), and fewer than "
                f"{self.n_layers}"
            )

    @property
    def period(self) -> int:
        return len(self.layer_kinds)

    @property
    def n_leading(self) -> int:
        return len(self.prefix.mixers) if self.prefix else 0

    @property
    def layer_mixers(self) -> tuple[str, ...]:
        """Every layer's mixer kind, in order: the prefix, then the
        periods repeated."""
        lead = self.prefix.mixers if self.prefix else ()
        return lead + tuple(
            self.layer_kinds[i % self.period][0]
            for i in range(self.n_layers - self.n_leading)
        )

    @property
    def layer_windows(self) -> tuple[int, ...]:
        """Every layer's window: how many positions back a query sees,
        its own included; 0 = all. A "gqa" layer takes the model-wide
        ``sliding_window`` (on the layers its pattern names)."""

        def window(i: int, kind: str) -> int:
            if kind == "swa":
                return self.gated.window
            if kind == "gqa" and self.sliding_window > 0:
                every = self.sliding_window_pattern
                return self.sliding_window if i % every == 0 else 0
            return 0

        return tuple(window(i, m) for i, m in enumerate(self.layer_mixers))

    @property
    def attn_kind(self) -> str:
        """The shape of the period's attention: "gqa", "latent", or
        "ssm" where it has none."""
        kind = next((m for m, _ in self.layer_kinds if m != "ssm"), "ssm")
        return "gqa" if kind in GQA_MIXERS else kind

    @property
    def ffn_kind(self) -> str:
        return self.layer_kinds[0][1]

    @property
    def mixer_counts(self) -> tuple[int, int]:
        """(layers that cache keys and values, state-space layers): the
        depths of the page pool and of the recurrent state."""
        n_ssm = sum(m == "ssm" for m in self.layer_mixers)
        return self.n_layers - n_ssm, n_ssm

    @property
    def n_kv_layers(self) -> int:
        return self.mixer_counts[0]

    def mixer_slot(self, j: int) -> tuple[str, int, int]:
        """Position ``j`` of a period: (its mixer kind, its index among
        the period's layers of that kind, how many of them a period has).
        Layer p * period + j reads row p * count + index of its kind's
        weight stack, pool or state."""
        kind = self.layer_kinds[j][0]
        same = [i for i, (m, _) in enumerate(self.layer_kinds) if (m == "ssm") == (kind == "ssm")]
        return kind, same.index(j), len(same)

    @property
    def kv_layout(self) -> tuple[int, int, int]:
        """(heads, "k" width, "v" width) of one cached token a layer.
        Latent layers cache one head: "k" is the shared rotated key
        (``rope_dim`` padded to whole lanes with zeros), "v" the
        compressed vector, which serves as the values AND as the
        unrotated part of the keys."""
        if self.latent is not None:
            return 1, self.latent.rope_pad, self.latent.kv_rank
        if self.ssm is not None:
            # The few attention layers beside state-space layers cache
            # their heads zero-padded to whole lanes (as ``rope_pad``
            # does), so that the paged kernel can cut and walk a row's
            # live pages: a narrower page goes to the grid kernel, which
            # visits the table's whole width, and XLA re-lays the whole
            # pool out around every step.
            width = -(-self.head_dim // LANES) * LANES
            return self.n_kv_heads, width, width
        return self.n_kv_heads, self.head_dim, self.head_dim

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def rope_scaling(self) -> tuple[float, float, float, float] | None:
        """(factor, low, high, original_max) for ops/rope.py, or None."""
        if not self.rope_scaling_factor:
            return None
        return (
            self.rope_scaling_factor,
            self.rope_low_freq_factor,
            self.rope_high_freq_factor,
            float(self.rope_original_max),
        )

    @property
    def attn_scale(self) -> float:
        import math

        if self.latent is not None:
            return self.latent.softmax_scale
        if self.attention_multiplier:
            return self.attention_multiplier
        return 1.0 / math.sqrt(self.query_pre_attn_scalar or self.head_dim)


def _llama(dim, n_layers, n_heads, n_kv_heads, ffn_dim, vocab=128256, **kw):
    return ModelConfig(
        vocab_size=vocab,
        dim=dim,
        n_layers=n_layers,
        n_heads=n_heads,
        n_kv_heads=n_kv_heads,
        head_dim=dim // n_heads,
        ffn_dim=ffn_dim,
        rope_theta=500000.0,
        **kw,
    )


def _mistral4(
    *, vocab, dim, n_layers, n_heads, q_rank, kv_rank, nope, rope, v_dim,
    n_routed, top_k, expert_dim, yarn, max_seq_len,
):
    return ModelConfig(
        vocab_size=vocab,
        dim=dim,
        n_layers=n_layers,
        n_heads=n_heads,
        n_kv_heads=n_heads,
        head_dim=nope + rope,
        ffn_dim=expert_dim,  # the shared expert's width
        rope_theta=10000.0,
        rms_eps=1e-6,
        max_seq_len=max_seq_len,
        layer_kinds=(("latent", "routed"),),
        latent=LatentAttention(
            q_rank=q_rank, kv_rank=kv_rank, nope_dim=nope, rope_dim=rope,
            v_dim=v_dim, yarn=yarn,
        ),
        experts=RoutedExperts(
            n_routed=n_routed, top_k=top_k, expert_dim=expert_dim, n_shared=1
        ),
    )


def _granite_hybrid(
    *, vocab, dim, n_layers, n_heads, n_kv_heads, head_dim, ffn_dim, ssm,
    max_seq_len,
):
    """Granite-4.0-H (HF ``granitemoehybrid`` with no routed experts):
    periods of five state-space layers, one attention layer, four
    state-space layers; the shared SwiGLU MLP after every mixer; no
    position embedding; tied embeddings; the four multipliers."""
    return ModelConfig(
        vocab_size=vocab,
        dim=dim,
        n_layers=n_layers,
        n_heads=n_heads,
        n_kv_heads=n_kv_heads,
        head_dim=head_dim,
        ffn_dim=ffn_dim,
        rms_eps=1e-5,
        tied_embeddings=True,
        max_seq_len=max_seq_len,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        attention_multiplier=0.015625,
        logits_scaling=8.0,
        rope=False,
        layer_kinds=(("ssm", "dense"),) * 5
        + (("gqa", "dense"),)
        + (("ssm", "dense"),) * 4,
        ssm=ssm,
    )


def _afmoe(
    *, vocab, dim, n_layers, n_heads, n_kv_heads, head_dim, window, leading,
    dense_ffn, period, n_routed, top_k, expert_dim, route_scale, max_seq_len,
):
    """Arcee's ``afmoe`` (Trinity): gated GQA attention with a norm a
    head, windowed rotated layers beside global unrotated ones, sandwich
    norms, the embedding times sqrt(dim); ``leading`` dense layers, then
    a sigmoid router with a selection bias over ``n_routed`` experts
    beside one shared expert. ``period`` is the attention kinds from the
    first routed layer on."""
    return ModelConfig(
        vocab_size=vocab,
        dim=dim,
        n_layers=n_layers,
        n_heads=n_heads,
        n_kv_heads=n_kv_heads,
        head_dim=head_dim,
        ffn_dim=expert_dim,  # the shared expert's width
        rope_theta=10000.0,
        rms_eps=1e-5,
        scale_embeddings=True,
        post_norms=True,
        max_seq_len=max_seq_len,
        layer_kinds=tuple((m, "routed") for m in period),
        gated=GatedAttention(window=window),
        prefix=DensePrefix(mixers=tuple(leading), ffn_dim=dense_ffn),
        experts=RoutedExperts(
            n_routed=n_routed, top_k=top_k, expert_dim=expert_dim, n_shared=1,
            routed_scaling=route_scale, scoring="sigmoid",
            # moves the chosen four of half of the tokens at the published
            # width, the chosen two of 7% at tiny (tests/test_afmoe.py)
            bias_std=0.01,
        ),
    )


# Named (family, size) → config. "tiny" sizes are for tests/CI: real family
# semantics, toy widths (lane-aligned: dim multiple of 128 where possible).
CONFIGS: dict[tuple[str, str], ModelConfig] = {
    # Llama-3 family (HF meta-llama/Meta-Llama-3-8B etc.). 1b/3b are
    # Llama-3.2 (tied embeddings, rope scaling factor 32, 128k context);
    # 8b/70b are base Llama-3 (unscaled rope, 8k).
    ("llama", "tiny"): _llama(256, 2, 4, 2, 512, vocab=512),
    ("llama", "1b"): _llama(
        2048, 16, 32, 8, 8192,
        tied_embeddings=True, rope_scaling_factor=32.0,
        max_seq_len=131072,
    ),
    ("llama", "3b"): _llama(
        3072, 28, 24, 8, 8192,
        tied_embeddings=True, rope_scaling_factor=32.0,
        max_seq_len=131072,
    ),
    ("llama", "8b"): _llama(4096, 32, 32, 8, 14336),
    ("llama", "70b"): _llama(8192, 80, 64, 8, 28672),
    # Mistral-7B. The named "7b" is v0.3 (rope theta 1e6, NO sliding
    # window) — v0.1's theta-1e4 + window-4096 combination is a different
    # checkpoint generation and must not be mixed with v0.3 fields (no
    # real checkpoint has both). "tiny" keeps a window so the windowed
    # code path stays covered by the mistral family tests.
    ("mistral", "tiny"): ModelConfig(
        vocab_size=512,
        dim=256,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        head_dim=64,
        ffn_dim=512,
        rope_theta=10000.0,
        sliding_window=128,
    ),
    # Toy widths with 7b's head layout in miniature (GQA 2:1, no window)
    # and 4 KV heads, so a tp=4 mesh has a head per device to shard —
    # what chip_smoke.py --chips 4 rehearses on four virtual CPU devices.
    ("mistral", "tiny-tp4"): ModelConfig(
        vocab_size=512,
        dim=256,
        n_layers=2,
        n_heads=8,
        n_kv_heads=4,
        head_dim=32,
        ffn_dim=512,
        rope_theta=1000000.0,
    ),
    ("mistral", "7b"): ModelConfig(
        vocab_size=32768,  # v0.3 extended vocabulary (v0.2 was 32000)
        dim=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        head_dim=128,
        ffn_dim=14336,
        rope_theta=1000000.0,
        max_seq_len=32768,
    ),
    # Gemma-2: sandwich norms, softcaps, tied+scaled embeddings, gelu,
    # alternating sliding window.
    ("gemma2", "tiny"): ModelConfig(
        vocab_size=512,
        dim=256,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        head_dim=64,
        ffn_dim=512,
        rope_theta=10000.0,
        activation="gelu",
        tied_embeddings=True,
        scale_embeddings=True,
        post_norms=True,
        logit_softcap=30.0,
        attn_softcap=50.0,
        sliding_window=128,
        sliding_window_pattern=2,
        norm_scale_plus_one=True,
    ),
    ("gemma2", "9b"): ModelConfig(
        vocab_size=256000,
        dim=3584,
        n_layers=42,
        n_heads=16,
        n_kv_heads=8,
        head_dim=256,
        ffn_dim=14336,
        rope_theta=10000.0,
        activation="gelu",
        tied_embeddings=True,
        scale_embeddings=True,
        post_norms=True,
        logit_softcap=30.0,
        attn_softcap=50.0,
        sliding_window=4096,
        sliding_window_pattern=2,
        norm_scale_plus_one=True,
    ),
    # Qwen-2: QKV bias, tied embeddings on small sizes.
    ("qwen2", "tiny"): ModelConfig(
        vocab_size=512,
        dim=256,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        head_dim=64,
        ffn_dim=512,
        rope_theta=1000000.0,
        qkv_bias=True,
    ),
    ("qwen2", "7b"): ModelConfig(
        vocab_size=152064,
        dim=3584,
        n_layers=28,
        n_heads=28,
        n_kv_heads=4,
        head_dim=128,
        ffn_dim=18944,
        rope_theta=1000000.0,
        qkv_bias=True,
    ),
    ("qwen2", "72b"): ModelConfig(
        vocab_size=152064,
        dim=8192,
        n_layers=80,
        n_heads=64,
        n_kv_heads=8,
        head_dim=128,
        ffn_dim=29568,
        rope_theta=1000000.0,
        qkv_bias=True,
    ),
    # Mistral-Small-4 (HF model_type "mistral4", the language model only):
    # every layer latent attention + 128 routed experts (top 4, softmax
    # router, renormalised) beside one shared expert; YaRN rope on 64 of
    # a head's 128 query dims, pairs interleaved. ``head_dim`` is the
    # query/key width (nope + rope); values are ``latent.v_dim`` wide.
    ("mistral4", "tiny"): _mistral4(
        vocab=512, dim=256, n_layers=2, n_heads=4, q_rank=64, kv_rank=128,
        nope=32, rope=32, v_dim=64, n_routed=8, top_k=2, expert_dim=128,
        yarn=YarnRope(
            factor=4.0, original_max=64, mscale_all_dim=1.0,
            query_scaling_beta=0.1,
        ),
        # short: off the chip, attention reads the whole table's width
        max_seq_len=2048,
    ),
    ("mistral4", "small-119b"): _mistral4(
        vocab=131072, dim=4096, n_layers=36, n_heads=32, q_rank=1024,
        kv_rank=256, nope=64, rope=64, v_dim=128, n_routed=128, top_k=4,
        expert_dim=2048,
        yarn=YarnRope(
            factor=128.0, original_max=8192, mscale_all_dim=1.0,
            query_scaling_beta=0.1,
        ),
        max_seq_len=32768,  # published 1,048,576; the ctx buffer is sized by it
    ),
    # Granite-4.0-H-Micro (HF model_type "granitemoehybrid"): 36 Mamba-2
    # layers beside 4 NoPE GQA layers (5, 15, 25, 35), 3.19 B parameters.
    # "tiny" is one whole period at lane-aligned toy widths; its chunk of
    # 32 lets a short prompt cross chunk boundaries.
    ("granitemoehybrid", "tiny"): _granite_hybrid(
        vocab=512, dim=128, n_layers=10, n_heads=2, n_kv_heads=1,
        head_dim=64, ffn_dim=256,
        ssm=StateSpace(n_heads=4, head_dim=64, state_dim=128, chunk=32),
        # short: off the chip, attention reads the whole table's width
        max_seq_len=2048,
    ),
    ("granitemoehybrid", "h-micro"): _granite_hybrid(
        vocab=100352, dim=2048, n_layers=40, n_heads=32, n_kv_heads=8,
        head_dim=64, ffn_dim=8192,
        ssm=StateSpace(n_heads=64, head_dim=64, state_dim=128, chunk=256),
        max_seq_len=32768,  # published 131,072; the ctx buffer is sized by it
    ),
    # Trinity-Large-Preview (HF model_type "afmoe", 400B-A13B): 60 layers
    # whose attention is windowed and rotated ("swa", window 4096) but for
    # every fourth, which is global and unrotated ("nope"): layer_types
    # s,s,s,f from layer 0. The first 6 layers have a dense FFN of 12288;
    # the 54 after them 256 experts of 3072 (sigmoid scores, top 4 by
    # score + bias, renormalised, times 2.448) beside one shared expert.
    # The routed layers begin at layer 6, so their period reads s,f,s,s.
    # A cut in depth (``get_config``'s ``n_layers``) keeps whole periods
    # of the published numbering: n_layers % 4 leading dense layers (the
    # published first ones), then n_layers // 4 periods s,s,s,f (the
    # published layers from 8 on). "tiny" has two leading layers and a
    # period of two, one of each kind in both (off the chip a layer's
    # attention gathers the whole table's width, so a test's time goes by
    # layers x heads x max_seq_len: few, few and short).
    ("afmoe", "tiny"): _afmoe(
        vocab=512, dim=128, n_layers=4, n_heads=2, n_kv_heads=1, head_dim=64,
        window=128, leading=("swa", "nope"), dense_ffn=512,
        period=("swa", "nope"), n_routed=8, top_k=2, expert_dim=128,
        route_scale=2.448, max_seq_len=1600,
    ),
    ("afmoe", "trinity-large"): _afmoe(
        vocab=200192, dim=3072, n_layers=60, n_heads=48, n_kv_heads=8,
        head_dim=128, window=4096,
        leading=("swa", "swa", "swa", "nope", "swa", "swa"), dense_ffn=12288,
        period=("swa", "nope", "swa", "swa"), n_routed=256, top_k=4,
        expert_dim=3072, route_scale=2.448, max_seq_len=262144,
    ),
    ("gemma2", "27b"): ModelConfig(
        vocab_size=256000,
        dim=4608,
        n_layers=46,
        n_heads=32,
        n_kv_heads=16,
        head_dim=128,
        ffn_dim=36864,
        rope_theta=10000.0,
        activation="gelu",
        tied_embeddings=True,
        scale_embeddings=True,
        post_norms=True,
        logit_softcap=30.0,
        attn_softcap=50.0,
        sliding_window=4096,
        sliding_window_pattern=2,
        norm_scale_plus_one=True,
        query_pre_attn_scalar=144.0,  # dim / n_heads, NOT head_dim
    ),
}


def family_of(cfg: ModelConfig) -> str:
    """The family whose presets have ``cfg``'s layer pattern ("" if none):
    what an error names, where only the config is in hand."""
    for (family, _), preset in CONFIGS.items():
        if set(preset.layer_kinds) == set(cfg.layer_kinds) and (
            preset.latent is None
        ) == (cfg.latent is None):
            return family
    return ""


def refuse_unwired(cfg: ModelConfig, what: str, serves: str):
    """Refuse aloud, by the family's name, what is not wired for it,
    rather than serve a wrong answer. ``serves``: what does serve it."""
    raise NotImplementedError(
        f"{family_of(cfg) or 'this family'}: {what} is not wired; {serves}"
    )


def refuse_beside_state_space(cfg: ModelConfig, what: str):
    """Refuse aloud what is not wired for a family with state-space
    layers, by the family's name, rather than serve a wrong state."""
    raise NotImplementedError(
        f"{family_of(cfg) or 'this family'}: {what} is not wired beside "
        "state-space layers (a recurrent state a sequence); the "
        "ContinuousBatcher serves it on one device, in the model dtype, "
        "with paged KV in the model dtype"
    )


def _cut_depth(cfg: ModelConfig, n_layers: int) -> ModelConfig:
    """``cfg`` at a depth of ``n_layers``. Behind a dense prefix the cut
    keeps whole periods of the published numbering: the first
    ``n_layers % period`` leading layers, then whole periods that begin
    where the published pattern does (its first period boundary behind
    the prefix), not in the middle of one where the prefix happens to
    end."""
    if cfg.prefix is None:
        return replace(cfg, n_layers=n_layers)
    keep = n_layers % cfg.period
    if not 0 < keep <= cfg.n_leading:
        raise ValueError(
            f"{n_layers} layers keep {keep} of {cfg.n_leading} leading "
            f"layers and whole periods of {cfg.period}: not between 1 "
            f"and {cfg.n_leading}"
        )
    shift = -cfg.n_leading % cfg.period
    return replace(
        cfg,
        n_layers=n_layers,
        layer_kinds=cfg.layer_kinds[shift:] + cfg.layer_kinds[:shift],
        prefix=replace(cfg.prefix, mixers=cfg.prefix.mixers[:keep]),
    )


def get_config(
    family: str,
    size: str,
    max_seq_len: int = 0,
    n_layers: int = 0,
    experts_held: tuple[int, int] | list[int] = (),
    vocab_rows: int = 0,
) -> ModelConfig:
    """The named config; nonzero ``max_seq_len`` / ``n_layers`` override
    its context length / depth, ``experts_held`` (first, count) and
    ``vocab_rows`` give this chip's share of the routed experts and of
    the vocabulary (registry ModelSpec fields)."""
    key = (family, size)
    if key not in CONFIGS:
        known = ", ".join(f"{f}/{s}" for f, s in sorted(CONFIGS))
        raise KeyError(f"no config for {family}/{size}; known: {known}")
    cfg = CONFIGS[key]
    if max_seq_len:
        cfg = replace(cfg, max_seq_len=max_seq_len)
    if n_layers:
        cfg = _cut_depth(cfg, n_layers)
    if experts_held:
        if cfg.experts is None:
            raise ValueError(f"{family}/{size} has no routed experts to share")
        first, count = (int(v) for v in experts_held)
        if not (0 <= first and 0 < count and first + count <= cfg.experts.n_routed):
            raise ValueError(
                f"experts_held {tuple(experts_held)} outside the "
                f"{cfg.experts.n_routed} routed experts"
            )
        cfg = replace(cfg, experts=replace(cfg.experts, held=(first, count)))
    if vocab_rows:
        if not 0 < vocab_rows <= cfg.vocab_size:
            raise ValueError(
                f"vocab_rows {vocab_rows} outside the vocabulary of "
                f"{cfg.vocab_size}"
            )
        cfg = replace(cfg, vocab_size=vocab_rows)
    return cfg
