"""Fused Pallas kernel for the hierarchical analytic allocator.

One frame's class queue is allocated in one fused walk: masked argmax
over each class's (M, L) cell slab, analytic chunk sizing by the f32
fit count, budget depletion — all on chip, without round-tripping the
shrinking ``gamma``/``eta`` budgets to HBM between classes.  Output is
the fixed-shape ``(take, start)`` cell pair (see
``repro.core.aggregation``): ``take[c, j, l]`` members of class ``c`` go
to cell ``(j, l)`` starting at member offset ``start[c, j, l]``.

Grid: ``(B, C / TC)`` — one frame per leading index, and the class axis
cut into chunks of ``TC`` classes that are walked in order.  The budgets
are a sequential carry across the whole class axis, so they live in VMEM
scratch that the first chunk of each frame loads and every later chunk
continues; only one chunk of the class tensors is resident at a time,
which is what lets city-scale frames (C ≈ 20k classes, M = 21, L = 10)
fit VMEM.  ``vmap`` (the fleet runner maps this kernel over replications
inside ``lax.scan``) prepends a grid axis and leaves ``pl.program_id(1)``
pointing at the class-chunk axis.

Layout per grid step, with the (M, L) cell grid flattened onto the lane
axis (``K = M * L``; flat index ``k = j * L + l``):

  cover/count  : (1, TC)  SMEM  class cover server / member count, int32
  srv          : (1, K)   VMEM  server index of each lane (``k // L``)
  us/v/u       : (TC, K)  VMEM  class candidate tensors, f32
  feas         : (TC, K)  VMEM  feasibility mask, f32 0/1
  gamma/eta    : (1, K)   VMEM  the frame's initial budgets, repeated over
                                each server's L lanes
  out take     : (TC, K)  VMEM  int32 members allocated per cell
  out start    : (TC, K)  VMEM  int32 first member offset per cell
  scratch      : (1, K)   VMEM  x2, the budget carry across chunks

As in the dense kernel, rows are read and written through refs, budgets
are lane-repeated so ``gamma[j]`` and ``eta[s]`` are lane masks and
max-reductions, and the argmax is a max followed by the lowest lane
holding it (``jnp.argmax``'s first-occurrence tie-break).

Bit-parity contract: the chunk-sizing arithmetic is op-for-op the f32
sequence of ``repro.core.aggregation.hier_cells_np`` and its jitted XLA
twin — the fit count :func:`fit_count`, ``min`` against the remainder in
f32 *before* the int32 cast (overflow guard for tiny costs), commit via
``budget + (-(f32(take) * cost))``.  Integer outputs must equal both
exactly (``tests/test_hier_parity.py`` is the three-way harness).

This module depends only on jax — never on ``repro.core`` (the core's
aggregation module imports *us*, and a reverse import would cycle).
Whether the kernel is compiled or interpreted follows
:func:`repro.kernels.gus_pallas.pallas_interpret`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gus_pallas import _lane_pick, lane_layout, pallas_interpret

__all__ = ["hier_cells_pallas", "fit_count", "CLASS_CHUNK"]

#: matches ``repro.core.aggregation._NEG`` — the masked-out cell score.
NEG = -1e30

#: classes per grid step.  Six (TC, K) f32/int32 blocks, double-buffered,
#: take 6 MiB at K <= 256 — inside the 16 MiB of VMEM a kernel may use by
#: default on v5e.
CLASS_CHUNK = 512


def fit_count(budget, cost):
    """``max{t : f32(t * cost) <= budget}`` for ``cost > 0``, as f32.

    ``floor(budget / cost)`` alone is not portable: a TPU's f32 divide is
    not correctly rounded, and where ``budget`` is an exact multiple of
    ``cost`` its floor can land one below NumPy's.  One correcting step
    each way, decided by correctly rounded f32 products, makes the count
    the same on every platform whenever the divide is within one of it.
    """
    q = jnp.floor(budget / cost)
    q = jnp.where(q * cost > budget, q - 1.0, q)
    return jnp.where((q + 1.0) * cost <= budget, q + 1.0, q)


def _hier_kernel(
    cover_ref, count_ref, srv_ref, us_ref, feas_ref, v_ref, u_ref,
    gamma_ref, eta_ref,
    take_ref, start_ref,
    gamma_sc, eta_sc,
    *, n_classes: int,
):
    @pl.when(pl.program_id(1) == 0)
    def _():  # first chunk of a frame: load its budgets into the carry
        gamma_sc[...] = gamma_ref[...]
        eta_sc[...] = eta_ref[...]

    srv = srv_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, srv.shape, 1)
    n_lanes = srv.shape[1]
    zeros = jnp.zeros(srv.shape, jnp.int32)

    def cls_body(c, budgets):
        gamma, eta = budgets
        s = cover_ref[0, c]
        cnt = count_ref[0, c]
        row = pl.ds(c, 1)
        us_c = us_ref[row, :]
        feas_c = feas_ref[row, :] != 0.0
        v_c = v_ref[row, :]
        u_c = u_ref[row, :]
        is_local = srv == s

        def cond(st):
            return st[-1]

        def chunk(st):
            rem, gamma, eta_s, take, start, used, _ = st
            ok = feas_c & (v_c <= gamma) & (is_local | (u_c <= eta_s))
            score = jnp.where(ok, us_c, NEG)
            best = jnp.max(score)
            flat = jnp.min(jnp.where(score == best, lane, n_lanes))
            any_ok = best > NEG
            sel = lane == flat
            j = _lane_pick(sel, srv, -1)
            vv = _lane_pick(sel, v_c, -jnp.inf)
            uv = _lane_pick(sel, u_c, -jnp.inf)
            g_j = _lane_pick(sel, gamma, -jnp.inf)
            offl = j != s
            rem_f = rem.astype(jnp.float32)
            cap_g = jnp.where(
                vv > 0, fit_count(g_j, jnp.where(vv > 0, vv, 1.0)), rem_f
            )
            cap_e = jnp.where(
                offl & (uv > 0),
                fit_count(eta_s, jnp.where(uv > 0, uv, 1.0)),
                rem_f,
            )
            t_f = jnp.minimum(rem_f, jnp.minimum(cap_g, cap_e))
            t = t_f.astype(jnp.int32)
            do = any_ok & (t >= 1)
            dt = jnp.where(do, t, 0)
            tf32 = dt.astype(jnp.float32)
            gamma = jnp.where(srv == j, gamma + (-(tf32 * vv)), gamma)
            eta_s = eta_s + jnp.where(offl, -(tf32 * uv), 0.0)
            start = jnp.where(sel & do & (take == 0), used, start)
            take = jnp.where(sel, take + dt, take)
            rem = rem - dt
            return rem, gamma, eta_s, take, start, used + dt, do & (rem > 0)

        st0 = (
            cnt,
            gamma,
            _lane_pick(is_local, eta, -jnp.inf),
            zeros,
            zeros,
            jnp.int32(0),
            jnp.any(feas_c) & (cnt > 0),
        )
        _, gamma, eta_s, take, start, _, _ = jax.lax.while_loop(cond, chunk, st0)
        take_ref[row, :] = take
        start_ref[row, :] = start
        return gamma, jnp.where(is_local, eta_s, eta)

    gamma, eta = jax.lax.fori_loop(
        0, n_classes, cls_body, (gamma_sc[...], eta_sc[...])
    )
    gamma_sc[...] = gamma
    eta_sc[...] = eta


def hier_cells_pallas(
    us, feas, v, u, cover, count, gamma, eta, *, interpret=None,
):
    """Run the fused hierarchical allocator on a batch of frames.

    Shapes (leading batch axis ``B`` required; ``repro.core.aggregation``
    adds it for single frames): ``us/feas/v/u`` ``(B, C, M, L)``;
    ``cover/count`` ``(B, C)``; ``gamma/eta`` ``(B, M)``.  Returns
    ``(take, start)`` int32 ``(B, C, M, L)``.  ``interpret=None`` resolves
    via :func:`repro.kernels.gus_pallas.pallas_interpret`.
    """
    if interpret is None:
        interpret = pallas_interpret()
    B, C, M, L = us.shape
    if C == 0:
        empty = jnp.zeros((B, 0, M, L), jnp.int32)
        return empty, empty
    K = M * L
    tc = min(C, CLASS_CHUNK)
    Cp = -(-C // tc) * tc  # zero-count padding classes are skipped

    def cls_rows(x, dtype):
        x = x.astype(dtype).reshape(B, C, -1)
        return jnp.pad(x, ((0, 0), (0, Cp - C), (0, 0)))

    gamma_x, srv = lane_layout(gamma, M, L)
    eta_x, _ = lane_layout(eta, M, L)
    scalars = pl.BlockSpec(
        (None, 1, tc), lambda b, k: (b, 0, k), memory_space=pltpu.SMEM
    )
    rows = pl.BlockSpec((None, tc, K), lambda b, k: (b, k, 0))
    budget = pl.BlockSpec((None, 1, K), lambda b, k: (b, 0, 0))
    take, start = pl.pallas_call(
        functools.partial(_hier_kernel, n_classes=tc),
        grid=(B, Cp // tc),
        in_specs=[scalars, scalars, pl.BlockSpec((1, K), lambda b, k: (0, 0))]
        + [rows] * 4 + [budget, budget],
        out_specs=[rows, rows],
        out_shape=[jax.ShapeDtypeStruct((B, Cp, K), jnp.int32)] * 2,
        scratch_shapes=[pltpu.VMEM((1, K), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(
        cls_rows(cover, jnp.int32).reshape(B, 1, Cp),
        cls_rows(count, jnp.int32).reshape(B, 1, Cp),
        srv,
        *(cls_rows(x, jnp.float32) for x in (us, feas, v, u)),
        gamma_x,
        eta_x,
    )
    return (take[:, :C].reshape(B, C, M, L), start[:, :C].reshape(B, C, M, L))
