"""Transformer numerics tests.

The reference tests everything above its transport seam with fakes
(SURVEY §4); our model layer has no reference analog, so the ground truth
here is (a) self-consistency — incremental decode must reproduce the full
forward — and (b) parity with the HuggingFace torch implementations of the
same architectures on tiny random checkpoints.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from adversarial_spec_tpu.models import transformer as T
from adversarial_spec_tpu.models.config import get_config

FAMILIES = ["llama", "mistral", "gemma2", "qwen2"]


def _full_forward(params, cfg, ids, total_len):
    B, S = ids.shape
    cache = T.init_cache(cfg, B, total_len, dtype=jnp.float32)
    positions = jnp.tile(jnp.arange(S, dtype=jnp.int32)[None, :], (B, 1))
    kv_valid = jnp.arange(total_len)[None, :] < total_len
    return T.forward(
        params, cfg, ids, positions, cache, jnp.int32(0), kv_valid
    )


@pytest.mark.parametrize("family", FAMILIES)
def test_incremental_decode_matches_full_forward(family):
    """Prefill(prefix) + per-token decode must equal one full forward."""
    cfg = get_config(family, "tiny")
    rng = jax.random.key(0)
    params = T.init_params(rng, cfg, dtype=jnp.float32)
    S, extra = 8, 4
    total = S + extra
    ids = jax.random.randint(jax.random.key(1), (1, total), 0, cfg.vocab_size)

    full_logits, _ = _full_forward(params, cfg, ids, total)

    # Prefill on the first S tokens, then decode the rest one at a time.
    cache = T.init_cache(cfg, 1, total, dtype=jnp.float32)
    positions = jnp.arange(S, dtype=jnp.int32)[None, :]
    kv_valid = jnp.arange(total)[None, :] >= 0
    logits, cache = T.forward(
        params, cfg, ids[:, :S], positions, cache, jnp.int32(0), kv_valid
    )
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(full_logits[:, :S]), rtol=2e-4, atol=2e-4
    )
    for i in range(extra):
        pos = jnp.array([[S + i]], dtype=jnp.int32)
        step_logits, cache = T.forward(
            params,
            cfg,
            ids[:, S + i : S + i + 1],
            pos,
            cache,
            jnp.int32(S + i),
            kv_valid,
        )
        np.testing.assert_allclose(
            np.asarray(step_logits[:, 0]),
            np.asarray(full_logits[:, S + i]),
            rtol=2e-4,
            atol=2e-4,
        )


def test_left_padding_invariance():
    """A row's logits must not depend on how much left-padding it has."""
    cfg = get_config("llama", "tiny")
    params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
    seq = jax.random.randint(jax.random.key(2), (1, 6), 0, cfg.vocab_size)
    total = 16

    def run(pad):
        S = pad + 6
        ids = jnp.concatenate(
            [jnp.zeros((1, pad), jnp.int32), seq], axis=1
        )
        cache = T.init_cache(cfg, 1, total, dtype=jnp.float32)
        positions = jnp.maximum(
            jnp.arange(S, dtype=jnp.int32)[None, :] - pad, 0
        )
        kv_valid = jnp.arange(total)[None, :] >= pad
        logits, _ = T.forward(
            params, cfg, ids, positions, cache, jnp.int32(0), kv_valid
        )
        return np.asarray(logits[:, -1])

    np.testing.assert_allclose(run(0), run(5), rtol=2e-4, atol=2e-4)


def test_sliding_window_masks_distant_tokens():
    """With a window of W, logits at position p must ignore tokens < p-W."""
    cfg = get_config("mistral", "tiny")  # window 128 — shrink via replace
    from dataclasses import replace

    cfg = replace(cfg, sliding_window=4, n_layers=1)
    params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
    total = 12
    ids_a = jax.random.randint(jax.random.key(3), (1, total), 0, cfg.vocab_size)
    # Change a token far outside the window of the last position.
    ids_b = ids_a.at[0, 0].set((ids_a[0, 0] + 1) % cfg.vocab_size)

    la, _ = _full_forward(params, cfg, ids_a, total)
    lb, _ = _full_forward(params, cfg, ids_b, total)
    # Last position attends only to the final 4 slots — identical logits.
    np.testing.assert_allclose(
        np.asarray(la[:, -1]), np.asarray(lb[:, -1]), rtol=1e-5, atol=1e-5
    )
    # But an early position does see the change.
    assert not np.allclose(np.asarray(la[:, 1]), np.asarray(lb[:, 1]))


@pytest.mark.parametrize(
    "family,hf_name",
    [("llama", "llama"), ("qwen2", "qwen2"), ("mistral", "mistral"),
     ("gemma2", "gemma2")],
)
def test_hf_parity_tiny(family, hf_name, tmp_path):
    """Our forward must match transformers' torch forward on the same
    random tiny checkpoint (validates both the architecture flags and the
    loader's weight mapping/transposes)."""
    torch = pytest.importorskip("torch")
    import transformers

    cfg = get_config(family, "tiny")
    kwargs = dict(
        vocab_size=cfg.vocab_size,
        hidden_size=cfg.dim,
        num_hidden_layers=cfg.n_layers,
        num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads,
        intermediate_size=cfg.ffn_dim,
        rope_theta=cfg.rope_theta,
        rms_norm_eps=cfg.rms_eps,
        max_position_embeddings=256,
        tie_word_embeddings=cfg.tied_embeddings,
    )
    if family == "llama":
        hf_cfg = transformers.LlamaConfig(**kwargs)
    elif family == "qwen2":
        hf_cfg = transformers.Qwen2Config(**kwargs)
    elif family == "mistral":
        hf_cfg = transformers.MistralConfig(
            **kwargs, sliding_window=cfg.sliding_window
        )
    else:
        hf_cfg = transformers.Gemma2Config(
            **kwargs,
            head_dim=cfg.head_dim,
            hidden_activation="gelu_pytorch_tanh",
            query_pre_attn_scalar=cfg.head_dim,
            attn_logit_softcapping=cfg.attn_softcap,
            final_logit_softcapping=cfg.logit_softcap,
            sliding_window=cfg.sliding_window,
        )
    torch.manual_seed(0)
    hf_model = transformers.AutoModelForCausalLM.from_config(hf_cfg)
    hf_model.eval()
    ckpt = tmp_path / "ckpt"
    hf_model.save_pretrained(ckpt, safe_serialization=True)

    from adversarial_spec_tpu.engine.loader import load_hf_checkpoint

    params = load_hf_checkpoint(ckpt, cfg, family, dtype=jnp.float32)

    ids = np.array([[1, 7, 42, 9, 100, 3, 250, 11]], dtype=np.int64)
    with torch.no_grad():
        hf_logits = hf_model(torch.from_numpy(ids)).logits.numpy()

    ours, _ = _full_forward(params, cfg, jnp.asarray(ids, jnp.int32), 8)
    np.testing.assert_allclose(
        np.asarray(ours), hf_logits, rtol=2e-3, atol=2e-3
    )


def test_attn_scale_override():
    """Gemma-2-27B scales queries by 1/sqrt(dim/n_heads)=1/sqrt(144), not
    1/sqrt(head_dim)=1/sqrt(128); other configs use head_dim."""
    import math

    c27 = get_config("gemma2", "27b")
    assert c27.query_pre_attn_scalar == 144.0
    assert abs(c27.attn_scale - 1 / math.sqrt(144)) < 1e-12
    c9 = get_config("gemma2", "9b")
    assert abs(c9.attn_scale - 1 / math.sqrt(c9.head_dim)) < 1e-12
    cl = get_config("llama", "8b")
    assert abs(cl.attn_scale - 1 / math.sqrt(cl.head_dim)) < 1e-12


def test_scale_changes_logits():
    """The configured attention scale must actually reach the kernels:
    same weights, different query_pre_attn_scalar → different logits."""
    from dataclasses import replace

    cfg = get_config("llama", "tiny")
    params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
    ids = jnp.array([[1, 7, 42, 9]], jnp.int32)
    a, _ = _full_forward(params, cfg, ids, 4)
    cfg2 = replace(cfg, query_pre_attn_scalar=float(cfg.head_dim) * 4)
    b, _ = _full_forward(params, cfg2, ids, 4)
    assert not np.allclose(np.asarray(a), np.asarray(b))


def test_count_params():
    cfg = get_config("llama", "tiny")
    params = T.init_params(jax.random.key(0), cfg)
    n = T.count_params(params)
    assert n > 0
    # Embedding + lm_head dominate: V*D*2 = 512*256*2.
    assert n > 2 * cfg.vocab_size * cfg.dim


class TestRopeScaling:
    """Llama-3.1/3.2 rope scaling (ops/rope.py:_llama3_scale)."""

    def test_llama3_scaling_matches_hf_formula(self):
        """Independent numpy re-derivation of HF rope_type="llama3"."""
        from adversarial_spec_tpu.ops.rope import rope_angles

        head_dim, theta = 64, 500000.0
        factor, low, high, orig = 32.0, 1.0, 4.0, 8192.0
        half = head_dim // 2
        freqs = 1.0 / theta ** (np.arange(half, dtype=np.float64) / half)
        # HF modeling_rope_utils._compute_llama3_parameters, re-derived.
        low_wl = orig / low
        high_wl = orig / high
        expected = []
        for f in freqs:
            wl = 2 * np.pi / f
            if wl < high_wl:
                expected.append(f)
            elif wl > low_wl:
                expected.append(f / factor)
            else:
                smooth = (orig / wl - low) / (high - low)
                expected.append((1 - smooth) * f / factor + smooth * f)
        expected = np.asarray(expected)

        pos = jnp.array([1.0])
        cos, sin = rope_angles(
            pos, head_dim, theta, scaling=(factor, low, high, orig)
        )
        # At position 1, angle == scaled frequency.
        got = np.arctan2(np.asarray(sin[0]), np.asarray(cos[0]))
        np.testing.assert_allclose(got, expected, rtol=1e-6)

    def test_scaling_changes_low_freqs_only(self):
        from adversarial_spec_tpu.ops.rope import rope_angles

        pos = jnp.array([100.0])
        plain = rope_angles(pos, 64, 500000.0)
        scaled = rope_angles(
            pos, 64, 500000.0, scaling=(32.0, 1.0, 4.0, 8192.0)
        )
        # Highest-frequency component (index 0) is untouched.
        np.testing.assert_allclose(plain[0][0, 0], scaled[0][0, 0])
        # Lowest-frequency component is stretched (angle shrinks).
        assert abs(float(scaled[1][0, -1])) < abs(float(plain[1][0, -1]))

    def test_named_configs_are_checkpoint_consistent(self):
        """ADVICE r1: each named config matches ONE real checkpoint gen."""
        c1b = get_config("llama", "1b")
        assert c1b.tied_embeddings and c1b.rope_scaling is not None
        c3b = get_config("llama", "3b")
        assert c3b.tied_embeddings and c3b.rope_scaling is not None
        c8b = get_config("llama", "8b")
        assert not c8b.tied_embeddings and c8b.rope_scaling is None
        # Mistral-7B v0.3: theta 1e6, NO sliding window, 32768 vocab.
        m7b = get_config("mistral", "7b")
        assert m7b.rope_theta == 1000000.0 and m7b.sliding_window == 0
        assert m7b.vocab_size == 32768


def test_hf_parity_llama3_rope_scaling(tmp_path):
    """Llama-3.2-style rope scaling (HF rope_type="llama3") against the
    real transformers implementation — long positions are where scaled
    and unscaled frequencies diverge, so the prompt exceeds the original
    8-position window the test config declares."""
    torch = pytest.importorskip("torch")
    import transformers
    from dataclasses import replace

    cfg = replace(
        get_config("llama", "tiny"),
        tied_embeddings=True,
        rope_scaling_factor=32.0,
        rope_original_max=8,  # tiny "original" window: positions past 8
        max_seq_len=256,      # exercise the scaled regime immediately
    )
    hf_cfg = transformers.LlamaConfig(
        vocab_size=cfg.vocab_size,
        hidden_size=cfg.dim,
        num_hidden_layers=cfg.n_layers,
        num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv_heads,
        intermediate_size=cfg.ffn_dim,
        rope_theta=cfg.rope_theta,
        rms_norm_eps=cfg.rms_eps,
        max_position_embeddings=256,
        tie_word_embeddings=True,
        rope_scaling={
            "rope_type": "llama3",
            "factor": cfg.rope_scaling_factor,
            "low_freq_factor": cfg.rope_low_freq_factor,
            "high_freq_factor": cfg.rope_high_freq_factor,
            "original_max_position_embeddings": cfg.rope_original_max,
        },
    )
    torch.manual_seed(1)
    hf_model = transformers.AutoModelForCausalLM.from_config(hf_cfg)
    hf_model.eval()
    ckpt = tmp_path / "ckpt"
    hf_model.save_pretrained(ckpt, safe_serialization=True)

    from adversarial_spec_tpu.engine.loader import load_hf_checkpoint

    params = load_hf_checkpoint(ckpt, cfg, "llama", dtype=jnp.float32)

    S = 24  # well past rope_original_max=8
    rng = np.random.default_rng(7)
    ids = rng.integers(1, cfg.vocab_size, (1, S))
    with torch.no_grad():
        hf_logits = hf_model(torch.from_numpy(ids)).logits.numpy()

    ours, _ = _full_forward(params, cfg, jnp.asarray(ids, jnp.int32), S)
    np.testing.assert_allclose(
        np.asarray(ours), hf_logits, rtol=2e-3, atol=2e-3
    )
    # Guard: scaling genuinely changes the output in this regime (the
    # parity above must not be vacuous).
    unscaled = replace(cfg, rope_scaling_factor=0.0)
    ours_unscaled, _ = _full_forward(
        params, unscaled, jnp.asarray(ids, jnp.int32), S
    )
    assert not np.allclose(
        np.asarray(ours), np.asarray(ours_unscaled), atol=1e-3
    )


class TestTransposedHead:
    """Tied-embedding configs materialize a [D, V] head copy at init/load
    (full-bandwidth decode matmul); it must be numerically interchangeable
    with the einsum over the [V, D] embed table."""

    def _tied_cfg(self):
        from dataclasses import replace

        return replace(get_config("llama", "tiny"), tied_embeddings=True)

    def test_logits_parity_with_einsum_path(self):
        cfg = self._tied_cfg()
        params = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
        assert "lm_head_t" in params
        ids = jnp.asarray([[3, 5, 7, 11]], jnp.int32)
        fast, _ = _full_forward(params, cfg, ids, ids.shape[1])
        slow_params = {k: v for k, v in params.items() if k != "lm_head_t"}
        slow, _ = _full_forward(slow_params, cfg, ids, ids.shape[1])
        np.testing.assert_allclose(
            np.asarray(fast), np.asarray(slow), rtol=1e-5, atol=1e-5
        )

    def test_optional(self):
        cfg = self._tied_cfg()
        params = T.init_params(
            jax.random.key(0), cfg, dtype=jnp.float32, transposed_head=False
        )
        assert "lm_head_t" not in params

    def test_loader_materializes_transposed_head(self, tmp_path):
        torch = pytest.importorskip("torch")
        import transformers

        cfg = self._tied_cfg()
        hf_cfg = transformers.LlamaConfig(
            vocab_size=cfg.vocab_size,
            hidden_size=cfg.dim,
            num_hidden_layers=cfg.n_layers,
            num_attention_heads=cfg.n_heads,
            num_key_value_heads=cfg.n_kv_heads,
            intermediate_size=cfg.ffn_dim,
            rope_theta=cfg.rope_theta,
            rms_norm_eps=cfg.rms_eps,
            tie_word_embeddings=True,
        )
        torch.manual_seed(0)
        hf_model = transformers.AutoModelForCausalLM.from_config(hf_cfg)
        ckpt = tmp_path / "ckpt"
        hf_model.save_pretrained(ckpt, safe_serialization=True)

        from adversarial_spec_tpu.engine.loader import load_hf_checkpoint

        params = load_hf_checkpoint(ckpt, cfg, "llama", dtype=jnp.float32)
        assert "lm_head_t" in params
        np.testing.assert_array_equal(
            np.asarray(params["lm_head_t"]),
            np.asarray(params["embed"]).T,
        )


class TestIndexedWeightStacks:
    """With the fused dequant-matmul on, the layer scan slices no
    quantized matmul stack: the kernels read layer l of the whole stack
    through a prefetched index (`_split_layers`). The step's results are
    the unfused path's; with the kernel off the stacks go on as the
    scan's operands, as before."""

    PAGE = 8
    # a dense GQA family with QKV bias; the routed, latent family
    CASES = {
        "qwen2": dict(),
        "mistral4": dict(experts_held=[2, 4], vocab_rows=384),
    }
    # int8 stacks: attention's four and the FFN's three; latent
    # attention's wq_a, wq_b, wkv_a, wkv_b, wo and the shared expert's
    # three (the routed experts' stacks are never the scan's)
    N_QUANT = {"qwen2": 7, "mistral4": 8}

    @pytest.fixture(scope="class", params=list(CASES))
    def model(self, request):
        from adversarial_spec_tpu.ops import quant

        cfg = get_config(request.param, "tiny", **self.CASES[request.param])
        params = quant.quantize_params(
            T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
        )
        if cfg.qkv_bias:  # born zero: make them count
            for i, b in enumerate(("bq", "bk", "bv")):
                params["layers"][b] = 0.1 * jax.random.normal(
                    jax.random.key(i), params["layers"][b].shape
                )
        return request.param, cfg, params

    def _step_args(self, cfg, S, B=2, start=11):
        heads, k_dim, v_dim = cfg.kv_layout
        table = jnp.asarray([[1, 2, 3, -1], [4, 5, 6, -1]], jnp.int32)
        kk, kv, kt = jax.random.split(jax.random.key(3), 3)
        shape = (cfg.n_layers, 7, heads, self.PAGE)
        pool = {
            "k": jax.random.normal(kk, shape + (k_dim,), jnp.float32),
            "v": jax.random.normal(kv, shape + (v_dim,), jnp.float32),
        }
        tokens = jax.random.randint(kt, (B, S), 3, 259)
        q_pos = jnp.broadcast_to(start + jnp.arange(S), (B, S))
        wp = jnp.take_along_axis(table, q_pos // self.PAGE, axis=1)
        bounds = jnp.stack([jnp.zeros_like(q_pos), q_pos + 1], -1)
        return tokens, q_pos, pool, table, wp, q_pos % self.PAGE, bounds, q_pos

    @pytest.mark.parametrize("S", [1, 9])
    def test_paged_step_equals_the_unfused_path(self, model, S):
        _, cfg, params = model
        args = self._step_args(cfg, S)
        want, want_pool, _ = T.forward_paged_decode(params, cfg, *args)
        got, got_pool, _ = T.forward_paged_decode(
            params, cfg, *args, use_pallas_matmul=True, pallas_interpret=True
        )
        assert np.abs(np.asarray(want)).max() > 0.1
        # float32 from the same int8 weights: summation order alone
        np.testing.assert_allclose(got, want, atol=2e-4)
        for name in want_pool:
            np.testing.assert_allclose(got_pool[name], want_pool[name], atol=2e-4)

    def test_prefill_forward_equals_the_unfused_path(self, model):
        _, cfg, params = model
        tokens = jax.random.randint(jax.random.key(5), (2, 12), 3, 259)
        pos = jnp.broadcast_to(jnp.arange(12), (2, 12))

        def run(**kw):
            cache = T.init_cache(cfg, 2, 16, dtype=jnp.float32)
            return T.forward(
                params, cfg, tokens, pos, cache, jnp.int32(0),
                jnp.ones((2, 16), bool), **kw,
            )

        want, want_cache = run()
        got, got_cache = run(use_pallas_matmul=True, pallas_interpret=True)
        np.testing.assert_allclose(got, want, atol=2e-4)
        for name in want_cache:
            np.testing.assert_allclose(got_cache[name], want_cache[name], atol=2e-4)

    @staticmethod
    def _scanned_int8(fn, *args):
        """Shapes of the int8 operands that the program's layer scans
        slice (their `xs`)."""
        out = []
        for eqn in jax.make_jaxpr(fn)(*args).jaxpr.eqns:
            if eqn.primitive.name == "scan":
                first = eqn.params["num_consts"] + eqn.params["num_carry"]
                out += [
                    v.aval.shape
                    for v in eqn.invars[first:]
                    if v.aval.dtype == jnp.int8
                ]
        return out

    @pytest.mark.parametrize("program", ["paged_step", "prefill"])
    def test_the_layer_scan_slices_no_stack_a_kernel_reads(self, model, program):
        name, cfg, params = model
        if program == "paged_step":
            args = self._step_args(cfg, 9)
            call = lambda p, **kw: T.forward_paged_decode(p, cfg, *args, **kw)  # noqa: E731
        else:
            tokens = jnp.ones((2, 12), jnp.int32)
            pos = jnp.broadcast_to(jnp.arange(12), (2, 12))
            cache = T.init_cache(cfg, 2, 16, dtype=jnp.float32)
            call = lambda p, **kw: T.forward(  # noqa: E731
                p, cfg, tokens, pos, cache, jnp.int32(0),
                jnp.ones((2, 16), bool), **kw,
            )
        fused = self._scanned_int8(
            lambda p: call(p, use_pallas_matmul=True, pallas_interpret=True),
            params,
        )
        # what is left is what no kernel reads: latent attention's
        # wkv_b, which `_latent_up` dequantizes
        left = [params["layers"]["wkv_b"]["q"].shape] if cfg.latent else []
        assert fused == left
        off = self._scanned_int8(call, params)
        assert len(off) == self.N_QUANT[name]
        # a mesh over several devices turns the kernel off, flag or no flag
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("tp",))
        sharded = self._scanned_int8(
            lambda p: call(p, use_pallas_matmul=True, mesh=mesh), params
        )
        assert len(sharded) == self.N_QUANT[name]

    def test_count_of_indexed_stacks(self, model):
        name, cfg, params = model
        assert T.n_indexed_stacks(params, True, 36) == self.N_QUANT[name] - (
            cfg.latent is not None
        )
        assert T.n_indexed_stacks(params, False, 36) == 0
        plain = T.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
        assert T.n_indexed_stacks(plain, True, 36) == 0
