"""The state-space (Mamba-2) mixer's arithmetic, and the two kernels that
touch its recurrent state.

One layer keeps, for a sequence, a state ``S`` of ``[head_dim, state_dim]``
a head and the conv's last ``conv_width - 1`` inputs. A position updates it:

    S_t = exp(dt_t A) S_{t-1} + dt_t * x_t (outer) B_t        y_t = S_t C_t + D x_t

Over a span of W positions that is the same recurrence in matrix form
(``span_outputs``: the state's part ``exp(cum_t) (S_in C_t)`` plus the
span's own lower-triangular part), which reads the state ONCE, and a
commit (``commit_terms``: the state after the span's first ``n_keep``
positions), which reads and writes it once. A prefill chunk runs it over
``chunk`` positions at a time (``chunked_scan``, plain ``jax.numpy``), a
verify step or an admission's delta over the whole span.

The state is stored TRANSPOSED, ``[layers, rows, state_dim, heads *
head_dim]`` float32, so that both trips over it are plain matmuls with the
wide axis on the lanes: ``ys = C [W, N] @ S^T [N, HP]`` and ``S^T = decay *
S^T + B^T [N, W] @ xs [W, HP]``. On the chip the two are Pallas kernels
over the whole stack (``ssm_span_read``, ``ssm_span_update``: a row's state
of a layer is addressed by scalar-prefetched indices, never sliced out,
and the update is in place); elsewhere the same matmuls.

A verify step cannot know how many of its positions stand until the
logits are sampled, so it reads the state in the layer scan, leaves it
unwritten, and commits every layer after ``accept_spans`` from the span's
saved inputs (models/transformer.py ``commit_span``): two reads and one
write of the state a row a step, whatever was accepted.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HIGHEST = jax.lax.Precision.HIGHEST
# Lanes of the state a kernel program holds: [state_dim, 1024] float32 is
# 512 KB, in and out double-buffered 2 MB.
_BLOCK_LANES = 1024


def causal_conv(raw, window, w, b):
    """The depthwise causal conv and its silu over a span. ``raw``
    [B, W, C] are the span's inputs, ``window`` [B, K-1, C] the K-1 inputs
    before it, ``w`` [K, C] (tap K-1 multiplies the current position), ``b``
    [C]. Returns (activations [B, W, C] in ``raw``'s dtype, the inputs in
    order [B, K-1+W, C]: the window after n positions is rows n..n+K-2)."""
    K = w.shape[0]
    W = raw.shape[1]
    seq = jnp.concatenate([window.astype(raw.dtype), raw], axis=1)
    acc = b.astype(jnp.float32)[None, None, :]
    for k in range(K):
        acc = acc + w[k].astype(jnp.float32) * seq[:, k : k + W].astype(
            jnp.float32
        )
    return jax.nn.silu(acc).astype(raw.dtype), seq


def conv_window(seq, n_keep, width: int):
    """Rows ``n_keep[b] .. n_keep[b] + width - 1`` of ``seq`` [B, T, C]:
    the conv's window after the span's first ``n_keep`` positions."""
    idx = n_keep[:, None] + jnp.arange(width)[None, :]
    return jnp.take_along_axis(seq, idx[:, :, None], axis=1)


def span_outputs(x, b_in, c_in, dt, a, d, ys):
    """(y, cum) of a span: ``x`` [B, W, H, P], ``b_in`` / ``c_in``
    [B, W, N], ``dt`` [B, W, H] (after softplus; 0 where a position is
    not to count), ``a`` / ``d`` [H], ``ys`` [B, W, H, P] = S_in C_t; all
    float32. ``cum`` [B, W, H] is the running sum of dt * A."""
    W = x.shape[1]
    cum = jnp.cumsum(dt * a, axis=1)
    # matmul, not einsum: einsum lowers as a function of its own, whose
    # operations lose the caller's named scopes
    cb = jnp.matmul(c_in, jnp.swapaxes(b_in, 1, 2), precision=_HIGHEST)  # [B, j, i]
    seg = cum[:, :, None, :] - cum[:, None, :, :]  # [B, j, i, H]
    causal = (jnp.arange(W)[:, None] >= jnp.arange(W)[None, :])[None, :, :, None]
    m = jnp.exp(jnp.where(causal, seg, -jnp.inf)) * dt[:, None] * cb[..., None]
    own = jnp.matmul(  # [B, H, j, i] @ [B, H, i, P]
        jnp.transpose(m, (0, 3, 1, 2)), jnp.swapaxes(x, 1, 2), precision=_HIGHEST
    )
    y = (
        jnp.swapaxes(own, 1, 2)
        + jnp.exp(cum)[..., None] * ys
        + d[None, None, :, None] * x
    )
    return y, cum


def commit_terms(x, dt, cum, n_keep):
    """What advances the state over a span's first ``n_keep`` [B]
    positions: (decay [B, H], xs [B, W, H, P]) with S_out = decay * S_in +
    sum_i xs_i (outer) B_i."""
    W = dt.shape[1]
    kept = jnp.arange(W)[None, :] < n_keep[:, None]  # [B, W]
    last = jnp.take_along_axis(
        cum, jnp.maximum(n_keep - 1, 0)[:, None, None], axis=1
    )[:, 0]
    last = jnp.where(n_keep[:, None] > 0, last, 0.0)  # [B, H]
    w = jnp.exp(jnp.where(kept[..., None], last[:, None] - cum, -jnp.inf)) * dt
    return jnp.exp(last), w[..., None] * x


def state_read(state, c_in):
    """ys [B, W, HP] = C_t S_in over a dense state [B, N, HP]."""
    return jnp.matmul(c_in, state, precision=_HIGHEST)


def state_update(state, b_in, xs, decay):
    """decay * S + sum_i xs_i (outer) B_i over a dense state [B, N, HP];
    ``xs`` [B, W, HP], ``decay`` [B, HP]."""
    return decay[:, None, :] * state + jnp.matmul(
        jnp.swapaxes(b_in, 1, 2), xs, precision=_HIGHEST
    )


def chunked_scan(x, b_in, c_in, dt, a, d, state, chunk: int):
    """The chunked form over S positions, ``chunk`` at a time: (y
    [B, S, H, P], the state after the last position). ``state`` is dense
    [B, N, HP]; every position counts unless its ``dt`` is 0."""
    B, S, H, P = x.shape
    q = min(chunk, S)
    if S % q:
        raise ValueError(f"a span of {S} is no whole number of chunks of {q}")
    n = S // q

    def split(v):  # [B, S, ...] -> [n, B, q, ...]
        return jnp.swapaxes(v.reshape((B, n, q) + v.shape[2:]), 0, 1)

    def step(state, xs):
        # Named here too: layers that share this body share one lowered
        # function, whose operations do not carry the caller's scopes.
        with jax.named_scope("ssm.scan"):
            x_c, b_c, c_c, dt_c = xs
            ys = state_read(state, c_c).reshape(B, q, H, P)
            y, cum = span_outputs(x_c, b_c, c_c, dt_c, a, d, ys)
            decay, xw = commit_terms(
                x_c, dt_c, cum, jnp.full((B,), q, jnp.int32)
            )
            state = state_update(
                state, b_c, xw.reshape(B, q, H * P), jnp.repeat(decay, P, axis=1)
            )
            return state, y

    state, y = jax.lax.scan(
        step, state, (split(x), split(b_in), split(c_in), split(dt))
    )
    return jnp.swapaxes(y, 0, 1).reshape(B, S, H, P), state


def _pad_span(v, axis: int):
    """Zero-pad the span axis to whole sublanes (zeros add nothing)."""
    pad = -v.shape[axis] % 8
    if not pad:
        return v
    widths = [(0, 0)] * v.ndim
    widths[axis] = (0, pad)
    return jnp.pad(v, widths)


def _lanes(hp: int) -> int:
    return _BLOCK_LANES if hp % _BLOCK_LANES == 0 else hp


def _read_kernel(_layer, _rows, c_ref, s_ref, o_ref):
    o_ref[...] = jnp.dot(
        c_ref[...], s_ref[...],
        preferred_element_type=jnp.float32, precision=_HIGHEST,
    )


def _update_kernel(_layer, _rows, bt_ref, xs_ref, decay_ref, s_ref, o_ref):
    o_ref[...] = decay_ref[...] * s_ref[...] + jnp.dot(
        bt_ref[...], xs_ref[...],
        preferred_element_type=jnp.float32, precision=_HIGHEST,
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_span_read(stack, layer, rows, c_in, *, interpret: bool = False):
    """ys [B, W, HP] = C_t S_in for span position t of row b, the state
    read where it lies: ``stack`` [L, R, N, HP] float32, ``layer`` a
    scalar, ``rows`` [B] the state row of each span row, ``c_in``
    [B, W, N] float32."""
    B, W, N = c_in.shape
    HP = stack.shape[-1]
    rb = _lanes(HP)
    c_in = _pad_span(c_in, 1)
    Wp = c_in.shape[1]
    out = pl.pallas_call(
        _read_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, HP // rb),
            in_specs=[
                pl.BlockSpec((None, Wp, N), lambda b, r, *_: (b, 0, 0)),
                pl.BlockSpec(
                    (None, None, N, rb),
                    lambda b, r, layer_ref, rows_ref: (
                        layer_ref[0], rows_ref[b], 0, r
                    ),
                ),
            ],
            out_specs=pl.BlockSpec((None, Wp, rb), lambda b, r, *_: (b, 0, r)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, Wp, HP), jnp.float32),
        interpret=interpret,
        name="ssm_span_read",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        rows.astype(jnp.int32),
        c_in,
        stack,
    )
    return out[:, :W]


@functools.partial(
    jax.jit, static_argnames=("interpret",), donate_argnames=("stack",)
)
def ssm_span_update(
    stack, layer, rows, b_in, xs, decay, *, interpret: bool = False
):
    """``stack`` with row ``rows[b]`` of ``layer`` advanced in place:
    decay * S + sum_i xs_i (outer) B_i. ``b_in`` [B, W, N], ``xs``
    [B, W, HP], ``decay`` [B, HP], float32; ``rows`` are distinct."""
    B, W, N = b_in.shape
    HP = stack.shape[-1]
    rb = _lanes(HP)
    bt = jnp.swapaxes(_pad_span(b_in, 1), 1, 2)  # [B, N, Wp]
    xs = _pad_span(xs, 1)
    Wp = xs.shape[1]

    def state_at(b, r, layer_ref, rows_ref):
        return (layer_ref[0], rows_ref[b], 0, r)

    return pl.pallas_call(
        _update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, HP // rb),
            in_specs=[
                pl.BlockSpec((None, N, Wp), lambda b, r, *_: (b, 0, 0)),
                pl.BlockSpec((None, Wp, rb), lambda b, r, *_: (b, 0, r)),
                pl.BlockSpec((None, 1, rb), lambda b, r, *_: (b, 0, r)),
                pl.BlockSpec((None, None, N, rb), state_at),
            ],
            out_specs=pl.BlockSpec((None, None, N, rb), state_at),
        ),
        out_shape=jax.ShapeDtypeStruct(stack.shape, stack.dtype),
        # operands count the two prefetched scalars: the stack is the 6th
        input_output_aliases={5: 0},
        interpret=interpret,
        name="ssm_span_update",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        rows.astype(jnp.int32),
        bt,
        xs,
        decay[:, None, :],
        stack,
    )
