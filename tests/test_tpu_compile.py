"""Described-topology compiles: the serving path's device kernels, built by
the real TPU compiler for a v5e that is described, not attached.

Interpret-mode tests (tests/test_pallas.py) prove the kernels' math; they
cannot see what Mosaic refuses — a slice off the tiling, too much VMEM, a
block no layout admits. These compile each kernel at the shapes the batcher
gives it for Mistral-7B-v0.3 and Qwen2-7B and assert the compiled text holds
a `tpu_custom_call`: a kernel that silently fell back to the XLA path fails
here, at no chip time. Compiles, not runs — nothing here is a speed.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from adversarial_spec_tpu.models.config import get_config
from adversarial_spec_tpu.ops import pallas_paged, quant

PAGE = 64  # ContinuousBatcher's page size
B, GAMMA = 4, 8  # four opponents; default draft length
N_PAGES = 257  # a 16k-token pool + the trash page
TABLE_WIDTH = 32768 // PAGE  # the benchmark's entries ask max_seq_len 32768
# decode rows, verify rows (B·(γ+1)), one admission prefill chunk, a
# cached prompt's admission span of 128
ROW_COUNTS = {
    "decode": B, "verify": B * (GAMMA + 1), "prefill": 512, "admission": 128,
}
MODELS = {
    "mistral-7b": get_config("mistral", "7b"),
    "qwen2-7b": get_config("qwen2", "7b"),
}


@pytest.fixture(scope="module")
def chip():
    """One described v5e chip to place shapes on. The persistent compile
    cache is off around these: such a compile is written to it but cannot
    be read back without a chip, and the next run would warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu, or one that cannot describe v5e
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _shape(chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _pool(chip, cfg, dtype):
    """One pool array as the batcher holds it: every layer's pages."""
    return _shape(
        chip, (cfg.n_layers, N_PAGES, cfg.n_kv_heads, PAGE, cfg.head_dim), dtype
    )


ADMISSION_SPAN = 64  # an unchanged document's delta, padded (one row)


@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize(
    "entry",
    [
        "decode", "verify_span", "decode_int8_kv", "verify_span_int8_kv",
        "admission_span",
    ],
)
def test_paged_attention_compiles(chip, model, entry):
    """The paged-attention entry points over a bf16 pool and over an int8
    pool with its scale pages, reading one layer's pages out of the whole
    pool by index — as forward_paged_decode calls them. An admission's
    span of 64 positions is 256 (448) query rows a KV head: more than the
    walk's VMEM holds at once, so it goes in parts (`_queries_per_call`)."""
    cfg = MODELS[model]
    span = {"verify_span": GAMMA + 1, "admission_span": ADMISSION_SPAN}.get(
        entry.removesuffix("_int8_kv"), 0
    )
    rows = 1 if entry == "admission_span" else B
    int8_kv = entry.endswith("int8_kv")
    q_shape = (rows, span) if span else (rows,)
    q = _shape(chip, q_shape + (cfg.n_heads, cfg.head_dim), jnp.bfloat16)
    pool = _pool(chip, cfg, jnp.int8 if int8_kv else jnp.bfloat16)
    table = _shape(chip, (rows, TABLE_WIDTH), jnp.int32)
    # verify: per-position (starts, ends); decode: one (start, end) a row
    windows = (
        [_shape(chip, (rows, span), jnp.int32)] * 2
        if span
        else [_shape(chip, (rows, 2), jnp.int32)]
    )
    scales = (
        [_shape(chip, pool.shape[:-1] + (1,), jnp.float32)] * 2
        if int8_kv
        else []
    )
    kernel = (
        pallas_paged.paged_decode_attention_mq
        if span
        else pallas_paged.paged_decode_attention
    )

    def fn(q, k, v, table, layer, *rest):
        window, sc = rest[: len(windows)], rest[len(windows) :]
        qkw = dict(k_scale=sc[0], v_scale=sc[1]) if sc else {}
        return kernel(
            q, k, v, table, *window, layer=layer,
            attn_softcap=cfg.attn_softcap, scale=cfg.attn_scale, **qkw,
        )

    text = _compiled_text(
        fn, q, pool, pool, table, _shape(chip, (), jnp.int32), *windows, *scales
    )
    assert "tpu_custom_call" in text


def _weight_shapes(cfg):
    """(in, out) of the FFN up-projection and the output head — the
    widest per-layer matmul and the one odd, vocabulary-sized one."""
    return {"ffn_up": (cfg.dim, cfg.ffn_dim), "head": (cfg.dim, cfg.vocab_size)}


@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("fmt", ["int8", "int4"])
@pytest.mark.parametrize("rows", ROW_COUNTS)
@pytest.mark.parametrize("weight", ["ffn_up", "head"])
def test_dequant_matmul_compiles(chip, model, fmt, rows, weight):
    """ops.quant.matmul(use_pallas=True) — the dispatcher the forwards
    call, so a shape `fused_supported` turns away (and hands to XLA
    without a word) fails here."""
    k, n = _weight_shapes(MODELS[model])[weight]
    x = _shape(chip, (ROW_COUNTS[rows], k), jnp.bfloat16)
    if fmt == "int8":
        w = {"q": _shape(chip, (k, n), jnp.int8)}
    else:
        w = {"q4": _shape(chip, (k // 2, n), jnp.int8)}
    w["scale"] = _shape(chip, (1, n), jnp.float32)
    text = _compiled_text(
        lambda x, w: quant.matmul(x, w, use_pallas=True), x, w
    )
    assert "tpu_custom_call" in text


def _on_chip(chip, tree):
    return jax.tree.map(lambda s: _shape(chip, s.shape, s.dtype), tree)


def _int8_params(chip, cfg, **init_kw):
    """Shapes of the int8 checkpoint as the registry serves it."""
    from adversarial_spec_tpu.models.transformer import init_params

    return _on_chip(
        chip,
        jax.eval_shape(
            lambda: quant.quantize_params(
                init_params(jax.random.key(0), cfg, jnp.bfloat16, **init_kw),
                fmt="int8",
            )
        ),
    )


def _compiled_verify_step(chip, cfg, params, n_pages):
    """The batcher's whole verify program (`scheduler_spec_chunk`: draft,
    a span of γ+1 through every layer, accept) over a bfloat16 pool of
    `n_pages`, compiled for the described chip."""
    from adversarial_spec_tpu.engine import scheduler
    from adversarial_spec_tpu.engine.kvcache import (
        PagedCacheLayout,
        init_page_pool,
    )

    heads, k_dim, v_dim = cfg.kv_layout
    layout = PagedCacheLayout(
        n_pages=n_pages, page_size=PAGE, n_layers=cfg.n_layers,
        n_kv_heads=heads, head_dim=k_dim, v_dim=v_dim,
    )
    pool = _on_chip(
        chip, jax.eval_shape(lambda: init_page_pool(layout, jnp.bfloat16))
    )
    row = _shape(chip, (B,), jnp.int32)
    key = jax.eval_shape(lambda: jax.random.key(0))
    return scheduler.scheduler_spec_chunk.lower(
        params, cfg, pool,
        _shape(chip, (B, cfg.max_seq_len // PAGE), jnp.int32),
        _shape(chip, (B, cfg.max_seq_len), jnp.int32),
        row, row, row, row, row, row, row, row,
        _shape(chip, (B,), jnp.bool_),
        _shape(chip, (B, 128), jnp.int32),
        _shape(chip, (1,), jnp.int32),
        _shape(chip, key.shape, key.dtype),
        _shape(chip, (), jnp.float32),
        _shape(chip, (), jnp.float32),
        gamma=GAMMA, greedy=True, top_k=0, use_top_p=False,
        use_pallas=True, use_pallas_matmul=True, pallas_interpret=False,
    ).compile()


def _layer_weight_shapes(cfg):
    """(in, out) of a dense layer's distinct int8 matrices."""
    qd, kd = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    return {
        "wq": (cfg.dim, qd), "wk": (cfg.dim, kd),
        "w_up": (cfg.dim, cfg.ffn_dim), "w_down": (cfg.ffn_dim, cfg.dim),
    }


@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("rows", ["decode", "verify", "admission"])
@pytest.mark.parametrize("weight", ["wq", "wk", "w_up", "w_down"])
def test_dequant_matmul_reads_a_layer_of_the_stack_in_place(
    chip, model, rows, weight
):
    """The decode step's form of the kernel: the operand is the whole
    [L, K, N] stack and the layer a prefetched scalar. Every distinct
    matrix of a layer (whole-K and split-K plans, K 3584 .. 18944); no
    int8 value of the program but the stack itself: nothing is sliced
    out of it. At these weight-bound row counts every matrix streams
    blocks of at least 1.75 MiB (`w_down` by a widened output block)
    and the plan fits the chip's VMEM."""
    import re

    from adversarial_spec_tpu.ops.pallas_quant import weight_block_bytes

    cfg = MODELS[model]
    k, n = _layer_weight_shapes(cfg)[weight]
    w = {
        "q": _shape(chip, (cfg.n_layers, k, n), jnp.int8),
        "scale": _shape(chip, (cfg.n_layers, 1, n), jnp.float32),
    }
    x = _shape(chip, (ROW_COUNTS[rows], k), jnp.bfloat16)
    assert weight_block_bytes(x, w) >= 1_835_008
    text = _compiled_text(
        lambda x, w, layer: quant.matmul(
            x, quant.StackedLayer(w, layer), use_pallas=True
        ),
        x, w, _shape(chip, (), jnp.int32),
    )
    assert "tpu_custom_call" in text
    assert set(re.findall(r"s8\[([\d,]+)\]", text)) == {f"{cfg.n_layers},{k},{n}"}


@pytest.mark.parametrize("model", MODELS)
def test_dense_verify_step_reads_its_weight_stacks_in_place(chip, model):
    """The batcher's whole verify program at full depth, int8: the only
    int8 values in it are the seven whole stacks and the head (no
    layer's, and no block of layers', slice of a stack: those copies
    were 57-66% of the busy device, PERF.md section 6, PR 33), every
    layer's seven matmuls and its attention are kernels, and the
    temporaries are a few MB (a four-layer block of one FFN stack was
    235 MB)."""
    import re

    cfg = MODELS[model]
    compiled = _compiled_verify_step(
        chip, cfg, _int8_params(chip, cfg), N_PAGES
    )
    text = compiled.as_text()
    # the rolled scan's body is in the text once
    assert text.count("tpu_custom_call") >= 8
    L = cfg.n_layers
    stacks = {
        f"{L},{k},{n}" for k, n in _layer_weight_shapes(cfg).values()
    } | {f"{L},{cfg.n_heads * cfg.head_dim},{cfg.dim}"}
    head = {f"{cfg.dim},{cfg.vocab_size}", f"{cfg.dim},{cfg.vocab_size},1"}
    assert set(re.findall(r"s8\[([\d,]+)\]", text)) == stacks | head
    assert compiled.memory_analysis().temp_size_in_bytes < 32 << 20


@pytest.mark.slow
def test_whole_decode_chunk_compiles_in_place(chip):
    """The batcher's whole decode program at Mistral-7B int8, full depth:
    every layer's kernels are in it, and the donated pool is updated in
    place — temporaries stay far below the pool (they were twice the
    pool while the layer scan restacked it and the scatter re-laid it
    out; a pool sized to the chip cannot pay that)."""
    from adversarial_spec_tpu.engine import scheduler
    from adversarial_spec_tpu.engine.kvcache import (
        PagedCacheLayout,
        init_page_pool,
    )
    from adversarial_spec_tpu.models.transformer import init_params

    cfg = MODELS["mistral-7b"]

    def on_chip(tree):
        return jax.tree.map(lambda s: _shape(chip, s.shape, s.dtype), tree)

    params = on_chip(
        jax.eval_shape(
            lambda: quant.quantize_params(
                init_params(jax.random.key(0), cfg, jnp.bfloat16), fmt="int8"
            )
        )
    )
    layout = PagedCacheLayout(
        n_pages=N_PAGES, page_size=PAGE, n_layers=cfg.n_layers,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
    )
    pool = on_chip(jax.eval_shape(lambda: init_page_pool(layout, jnp.bfloat16)))
    pool_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pool))
    row = _shape(chip, (B,), jnp.int32)
    key = jax.eval_shape(lambda: jax.random.key(0))
    compiled = scheduler.scheduler_decode_chunk.lower(
        params, cfg, pool,
        _shape(chip, (B, cfg.max_seq_len // PAGE), jnp.int32),
        row, row, row, row, row,
        _shape(chip, (B,), jnp.bool_),
        _shape(chip, (B, 128), jnp.int32),
        _shape(chip, (1,), jnp.int32),
        _shape(chip, key.shape, key.dtype),
        _shape(chip, (), jnp.float32),
        _shape(chip, (), jnp.float32),
        chunk=32, greedy=False, top_k=0, use_top_p=False,
        use_pallas=True, use_pallas_matmul=True, pallas_interpret=False,
    ).compile()
    # the rolled scan's body, once: attention and the seven matmuls
    assert compiled.as_text().count("tpu_custom_call") >= 8
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 2


@pytest.mark.parametrize(
    "model,tokens",
    [("mistral-7b", B * (GAMMA + 1)), ("qwen2-7b", 128), ("mistral-7b", 512),
     ("latent", B * (GAMMA + 1))],
)
def test_span_write_compiles_in_place(chip, model, tokens):
    """A span's K/V by DMA (``ops/kv_write.py``) at the cells' pools: a
    verify step's 36 positions, admissions of 128 and 512, the latent
    pool's one head of 128 and 256 lanes. Each tile the span touches is
    cut out of the HBM pool (16 slots x every head; Mosaic refuses one
    slot) and the donated pool is updated in place: nothing pool-sized
    is copied, the temporaries are the tiles."""
    from adversarial_spec_tpu.ops import kv_write

    if model == "latent":
        heads, dims, layers = 1, (128, 256), 9
    else:
        cfg = MODELS[model]
        heads, dims, layers = cfg.n_kv_heads, (cfg.head_dim,) * 2, cfg.n_layers
    pool = {
        name: _shape(chip, (layers, N_PAGES, heads, PAGE, d), jnp.bfloat16)
        for name, d in zip("kv", dims)
    }
    new = {
        name: _shape(chip, (tokens, heads, d), jnp.bfloat16)
        for name, d in zip("kv", dims)
    }
    where = _shape(chip, (tokens,), jnp.int32)
    assert kv_write.span_write_kernel(pool, True)
    compiled = (
        jax.jit(
            lambda pool, new, layer, pages, offs: kv_write.write_span(
                pool, layer, new, pages, offs, use_pallas=True
            ),
            donate_argnums=0,
        )
        .lower(pool, new, _shape(chip, (), jnp.int32), where, where)
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


# -- Mistral-Small-4: the latent cache and the routed experts ---------------

M4 = get_config(
    "mistral4", "small-119b", n_layers=9, experts_held=(0, 32),
    vocab_rows=32768,
)


@pytest.mark.parametrize(
    "rows,span", [(B, GAMMA + 1), (1, ADMISSION_SPAN)], ids=["verify", "admission"]
)
def test_paged_latent_attention_compiles(chip, rows, span):
    """The latent verify kernel at the published shapes (32 heads on one
    shared 256 + 64-wide latent, a span of γ+1) over a 512-page table: the
    walk cuts [page, 256] and [page, 128] pages out of the two pools. An
    admission's 64 positions are 2,048 query rows on the one key: in parts."""
    la = M4.latent
    heads, k_dim, v_dim = M4.kv_layout
    q_lat = _shape(chip, (rows, span, M4.n_heads, la.kv_rank), jnp.bfloat16)
    q_rot = _shape(chip, (rows, span, M4.n_heads, la.rope_pad), jnp.bfloat16)
    r_pool = _shape(chip, (M4.n_layers, N_PAGES, heads, PAGE, k_dim), jnp.bfloat16)
    c_pool = _shape(chip, (M4.n_layers, N_PAGES, heads, PAGE, v_dim), jnp.bfloat16)
    window = _shape(chip, (rows, span), jnp.int32)

    def fn(q_lat, q_rot, r, c, table, layer, starts, ends):
        return pallas_paged.paged_latent_attention_mq(
            q_lat, q_rot, r, c, table, starts, ends,
            scale=M4.attn_scale, layer=layer,
        )

    text = _compiled_text(
        fn, q_lat, q_rot, r_pool, c_pool,
        _shape(chip, (rows, TABLE_WIDTH), jnp.int32),
        _shape(chip, (), jnp.int32), window, window,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows", ["verify", "prefill", "admission"])
@pytest.mark.parametrize("weight", ["we_up", "we_down"])
def test_grouped_dequant_matmul_compiles(chip, rows, weight):
    """The grouped int8 matmul over the held experts' stack of every
    layer ([9, 32, in, out]), at the row tiles the routed layer picks for
    a verify span, for a prefill chunk and for an admission's span (8, 64
    and 16 rows a tile)."""
    from adversarial_spec_tpu.models import moe
    from adversarial_spec_tpu.ops import pallas_quant

    ex = M4.experts
    k, n = {
        "we_up": (M4.dim, ex.expert_dim), "we_down": (ex.expert_dim, M4.dim)
    }[weight]
    pairs = ROW_COUNTS[rows] * ex.top_k
    bm = moe._tile_rows(pairs, ex.n_held)
    n_tiles = -(-pairs // bm) + ex.n_held
    text = _compiled_text(
        lambda x, q, s, layer, tg, nl: pallas_quant.matmul_int8_grouped(
            x, q, s, layer, tg, nl, bm=bm
        ),
        _shape(chip, (n_tiles * bm, k), jnp.bfloat16),
        _shape(chip, (M4.n_layers, ex.n_held, k, n), jnp.int8),
        _shape(chip, (M4.n_layers, ex.n_held, 1, n), jnp.float32),
        _shape(chip, (), jnp.int32),
        _shape(chip, (n_tiles,), jnp.int32),
        _shape(chip, (), jnp.int32),
    )
    assert "tpu_custom_call" in text


def _compiled_paged_admission(chip, cfg, params, n_pages, width):
    """The batcher's whole admission of a cached prompt
    (`scheduler.paged_admission`: the delta as a span of one row over the
    pool, the first token, the slot's rows) compiled for the described
    chip, speculation on."""
    from adversarial_spec_tpu.engine import scheduler
    from adversarial_spec_tpu.engine.kvcache import (
        PagedCacheLayout,
        init_page_pool,
    )

    heads, k_dim, v_dim = cfg.kv_layout
    layout = PagedCacheLayout(
        n_pages=n_pages, page_size=PAGE, n_layers=cfg.n_layers,
        n_kv_heads=heads, head_dim=k_dim, v_dim=v_dim,
    )
    pool = _on_chip(
        chip, jax.eval_shape(lambda: init_page_pool(layout, jnp.bfloat16))
    )
    table_width = cfg.max_seq_len // PAGE
    row, one = _shape(chip, (B,), jnp.int32), _shape(chip, (), jnp.int32)
    rows = {name: row for name in scheduler._ROW_STATE}
    rows["page_table"] = _shape(chip, (B, table_width), jnp.int32)
    rows["active"] = _shape(chip, (B,), jnp.bool_)
    key = jax.eval_shape(lambda: jax.random.key(0))
    return scheduler.paged_admission.lower(
        params, cfg, pool, rows,
        _shape(chip, (B, 128), jnp.int32),
        _shape(chip, (B, cfg.max_seq_len), jnp.int32),
        _shape(chip, (1, width), jnp.int32), one, one,
        _shape(chip, (table_width,), jnp.int32), one, one,
        _shape(chip, (1,), jnp.int32),
        _shape(chip, key.shape, key.dtype),
        _shape(chip, (), jnp.float32),
        _shape(chip, (), jnp.float32),
        _shape(chip, (cfg.max_seq_len,), jnp.int32), one, one,
        greedy=True, top_k=0, table_pages=table_width, use_top_p=False,
        use_pallas=True, use_pallas_matmul=True, pallas_interpret=False,
    ).compile()


@pytest.mark.parametrize(
    "model,width",
    [("mistral-7b", 64), ("qwen2-7b", 64), ("mistral-small-4", 64),
     ("mistral-7b", 512)],
)
def test_paged_admission_compiles_in_place(chip, model, width):
    """A cached prompt's whole admission at the cells' sizes: the layer's
    matmuls and its attention are kernels (the walk in
    `_queries_per_call`'s parts), the donated pool is updated in place and
    the temporaries are megabytes: no dense cache (1.07 GB at Mistral-7B
    for a 5.3k-token prompt) and no gathered prefix (0.7 GB)."""
    cfg = M4 if model == "mistral-small-4" else MODELS[model]
    init_kw = dict(expert_quant="int8") if cfg is M4 else {}
    compiled = _compiled_paged_admission(
        chip, cfg, _int8_params(chip, cfg, **init_kw), 513, width
    )
    # the rolled scan's body, once: attention at least once, the dense
    # int8 matmuls (and the routed family's three grouped ones)
    assert compiled.as_text().count("tpu_custom_call") >= 8
    assert compiled.memory_analysis().temp_size_in_bytes < 32 << 20


def test_latent_verify_step_compiles_in_place(chip):
    """The batcher's whole verify program at the cell's share of
    Mistral-Small-4 (9 layers, 32 of 128 experts, int8): both new kernels
    are in every layer, the expert stacks are read by index (no value of
    the program is a layer's or a block of layers' slice of a stack, and
    the temporaries stay under one layer's 805 MB of experts), and the
    donated latent pool is updated in place."""
    import re

    compiled = _compiled_verify_step(
        chip, M4, _int8_params(chip, M4, expert_quant="int8"), 513
    )
    text = compiled.as_text()
    # the rolled scan's body, once: the latent attention, three grouped
    # matmuls and the seven dense int8 ones
    assert text.count("tpu_custom_call") >= 1 + 3 + 7
    stacks = set(re.findall(r"s8\[((?:\d+,)*32,(?:4096,2048|2048,4096))\]", text))
    assert stacks == {"9,32,4096,2048", "9,32,2048,4096"}, stacks
    one_layer_of_experts = 32 * 3 * M4.dim * M4.experts.expert_dim
    assert compiled.memory_analysis().temp_size_in_bytes < one_layer_of_experts


def test_latent_admission_prefill_reads_the_stacks_through_the_kernel(chip):
    """The batcher's admission prefill chunk at the same share, traced
    with the flag the scheduler gives a routed family
    (`_prefill_pallas_matmul`): a chunk of 64 tokens after a cached prefix
    in a 6,144-slot admission cache. The layer scan's body (rolled: it is
    in the text once) holds the three grouped matmuls and the dense int8
    ones, and the temporaries stay under one layer's experts (44 MB
    here). Without the flag there is no kernel in it and every row tile
    gathers a whole expert matrix out of the stack: 3.6 GB of temporaries
    here, 150-250 ms a chunk on the chip (PERF.md section 6, PR 32)."""
    from adversarial_spec_tpu.engine.generate import prefill_chunk
    from adversarial_spec_tpu.models.transformer import init_cache, init_params

    def on_chip(tree):
        return jax.tree.map(lambda s: _shape(chip, s.shape, s.dtype), tree)

    params = on_chip(
        jax.eval_shape(
            lambda: quant.quantize_params(
                init_params(
                    jax.random.key(0), M4, jnp.bfloat16, expert_quant="int8"
                ),
                fmt="int8",
            )
        )
    )
    cache = on_chip(
        jax.eval_shape(lambda: init_cache(M4, 1, 6144, dtype=jnp.bfloat16))
    )
    compiled = prefill_chunk.lower(
        params, M4, _shape(chip, (1, 64), jnp.int32),
        _shape(chip, (1,), jnp.int32), cache, _shape(chip, (), jnp.int32),
        use_pallas_matmul=True,
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3 + 8
    one_layer_of_experts = 32 * 3 * M4.dim * M4.experts.expert_dim
    assert compiled.memory_analysis().temp_size_in_bytes < one_layer_of_experts


@pytest.mark.parametrize("span,rows", [(GAMMA + 1, B), (64, 1), (256, 1)])
def test_state_space_span_kernels_compile(chip, span, rows):
    """The two kernels that touch the recurrent state, at granite-4.0-h-micro's
    shapes: a verify step's span over four rows, and an admission's delta over
    one, read out of and written into the whole [36, rows, 128, 4096] float32
    stack by prefetched layer and row indices; the update is in place (no
    temporary of the stack's size)."""
    from adversarial_spec_tpu.ops import ssm

    cfg = get_config("granitemoehybrid", "h-micro")
    sp = cfg.ssm
    stack = _shape(chip, (cfg.mixer_counts[1], B, sp.state_dim, sp.inner_dim), jnp.float32)
    idx = [_shape(chip, (), jnp.int32), _shape(chip, (rows,), jnp.int32)]
    bc = _shape(chip, (rows, span, sp.state_dim), jnp.float32)
    assert "tpu_custom_call" in _compiled_text(ssm.ssm_span_read, stack, *idx, bc)
    compiled = (
        jax.jit(ssm.ssm_span_update.__wrapped__, donate_argnums=0)
        .lower(
            stack, *idx, bc, _shape(chip, (rows, span, sp.inner_dim), jnp.float32),
            _shape(chip, (rows, sp.inner_dim), jnp.float32),
        )
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


# The cell's share of Trinity-Large (`afmoe`): one leading dense layer and
# two periods of windowed and global gated attention, 32 of 256 experts, an
# eighth of the vocabulary, tables of 32,768 positions.
TRINITY = get_config(
    "afmoe", "trinity-large", max_seq_len=32768, n_layers=9,
    experts_held=(0, 32), vocab_rows=25024,
)


@pytest.mark.parametrize("program", ["verify", "admission_128", "cold_chunk"])
def test_gated_window_stack_compiles_in_place(chip, program):
    """The batcher's programs at the cell's share of Trinity-Large, over a
    pool of 65,536 tokens. The verify step and a cached prompt's admission
    hold every layer's kernels (the leading layer's 5 attention matmuls, 3
    of its FFN and its walk; a period body's 4 x (5 + 3 shared + 3 grouped
    + the walk at 6 query heads a KV head)), read the int8 stacks and the
    expert stacks by a traced row and update the donated pool in place:
    temporaries of megabytes. A cold 512-token chunk into the 16,384-slot
    dense cache of a 13.5k-token prompt keeps XLA's attention: 2.6 GB of
    temporaries by this analysis (float32 scores of 48 heads x 512 x
    16,384; PERF.md section 7, "From PR 38" (ii))."""
    params = _int8_params(chip, TRINITY, expert_quant="int8")
    if program == "verify":
        compiled = _compiled_verify_step(chip, TRINITY, params, 1025)
        kernels, temp = 9 + 4 * 12, 64 << 20
    elif program == "admission_128":
        compiled = _compiled_paged_admission(chip, TRINITY, params, 1025, 128)
        kernels, temp = 9 + 4 * 12, 64 << 20
    else:
        from adversarial_spec_tpu.engine.generate import prefill_chunk
        from adversarial_spec_tpu.models.transformer import init_cache

        cache = _on_chip(
            chip,
            jax.eval_shape(lambda: init_cache(TRINITY, 1, 16384, dtype=jnp.bfloat16)),
        )
        compiled = prefill_chunk.lower(
            params, TRINITY, _shape(chip, (1, 512), jnp.int32),
            _shape(chip, (1,), jnp.int32), cache, _shape(chip, (), jnp.int32),
            use_pallas_matmul=True,
        ).compile()
        kernels, temp = 8 + 4 * 11, 3 << 30
    assert compiled.as_text().count("tpu_custom_call") >= kernels
    assert compiled.memory_analysis().temp_size_in_bytes < temp
