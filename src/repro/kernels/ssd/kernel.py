"""Pallas TPU kernel for the Mamba2 SSD chunk recurrence.

Hardware adaptation (DESIGN.md §2): SSD's chunked "state-space duality"
form is chosen over Mamba-1's elementwise selective scan precisely
because each chunk is matmul-shaped (MXU) instead of a length-L diagonal
recurrence (VPU-serial).

Grid: (batch*heads, chunks) with chunks sequential; the running state
(P x N) lives in VMEM scratch.  Per chunk (all fp32 in-VMEM):
    L        = exp(segsum(dA))           (Q x Q lower-triangular decay)
    y_diag   = ((C B^T) . L) x
    y_off    = (C h^T) . exp(cumsum dA)
    h        = h * exp(sum dA) + (B * decay_to_end)^T x
Inputs are pre-arranged by ops.py as x*(dt), dA = A*dt.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, da_ref, b_ref, c_ref, y_ref, hout_ref, h_scr, *,
                Q: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0].astype(jnp.float32)         # (Q, P)
    da = da_ref[0].astype(jnp.float32)       # (Q, 1) -> (Q,)
    da = da[:, 0]
    Bc = b_ref[0].astype(jnp.float32)        # (Q, N)
    Cc = c_ref[0].astype(jnp.float32)        # (Q, N)

    da_cs = jnp.cumsum(da)                   # (Q,)
    seg = da_cs[:, None] - da_cs[None, :]    # (Q, Q)
    causal = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    Lmat = jnp.where(causal, jnp.exp(seg), 0.0)

    scores = jax.lax.dot_general(
        Cc, Bc, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)  # (Q, Q)
    y_diag = jax.lax.dot((scores * Lmat).astype(jnp.float32), x,
                         preferred_element_type=jnp.float32)

    h = h_scr[...]                           # (N, P)
    y_off = jax.lax.dot(Cc * jnp.exp(da_cs)[:, None], h,
                        preferred_element_type=jnp.float32)  # (Q, P)

    decay_to_end = jnp.exp(da_cs[-1] - da_cs)               # (Q,)
    upd = jax.lax.dot_general(
        Bc * decay_to_end[:, None], x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)  # (N, P)
    h_scr[...] = h * jnp.exp(da_cs[-1]) + upd

    y_ref[0] = (y_diag + y_off).astype(y_ref.dtype)

    @pl.when(ci == pl.num_programs(1) - 1)
    def _done():
        hout_ref[0] = h_scr[...].astype(hout_ref.dtype)


def ssd_scan(xdt: jax.Array, dA: jax.Array, Bm: jax.Array, Cm: jax.Array,
             *, chunk: int = 256, interpret: bool = False):
    """xdt: (BH, L, P) inputs pre-multiplied by dt; dA: (BH, L) decay
    exponents (A*dt, negative); Bm/Cm: (BH, L, N).
    Returns (y (BH, L, P), final_state (BH, N, P))."""
    BH, L, P = xdt.shape
    N = Bm.shape[-1]
    chunk = min(chunk, L)
    assert L % chunk == 0, "ops.py pads L to a chunk multiple"
    nc = L // chunk

    grid = (BH, nc)
    kernel = functools.partial(_ssd_kernel, Q=chunk)
    y, hout = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, N, P), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, L, P), xdt.dtype),
            jax.ShapeDtypeStruct((BH, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xdt, dA[..., None], Bm, Cm)
    return y, hout
