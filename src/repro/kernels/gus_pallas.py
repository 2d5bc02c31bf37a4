"""Fused Pallas kernel for the GUS greedy assignment core.

One grid program schedules one frame: the per-candidate utility (Eq. 1),
hard feasibility, and the capacity-aware greedy argmax loop of Algorithm 1
all run fused in on-chip memory — the candidate tensors are loaded into
VMEM once and never round-trip to HBM between the utility computation and
the N sequential greedy steps.  The grid is the frame batch, so a fleet's
``R`` replications (or a Monte-Carlo sweep's stacked instances) become
``R`` independent grid programs.

Layout per program, with the (M, L) candidate grid flattened onto the lane
axis (``K = M * L``; flat index ``k = j * L + l``):

  cover/A/C/w_a/w_c : (1, N)  SMEM  per-request scalars
  scal              : (1, 2)  SMEM  [max_as, max_cs] normalizers
  srv               : (1, K)  VMEM  server index of each lane (``k // L``)
  acc/ctime/v/u     : (N, K)  VMEM  candidate tensors, f32
  avail             : (N, K)  VMEM  placement mask, f32 0/1
  gamma/eta         : (1, K)  VMEM  per-server budgets, repeated over each
                                    server's L lanes
  out j/l           : (1, N)  SMEM  int32 assignment (-1 = dropped)

Every block's last two axes are whole, which is what Mosaic requires of a
block that is not (8, 128)-aligned.

Greedy step ``i`` reads row ``i`` of each candidate tensor through its ref
and computes that row's utility and feasibility; the budgets ride the
``fori_loop`` carry in lane-repeated form, so ``gamma[j]`` and ``eta[s]``
are lane masks and max-reductions, never dynamic gathers or scatters.  The
argmax is a max followed by the lowest lane holding it — the same
first-occurrence tie-break as ``jnp.argmax``.

Bit-parity contract: the utility expression below is op-for-op the one in
:func:`repro.core.satisfaction.us_tensor`, the feasibility mask matches
:func:`~repro.core.satisfaction.hard_feasible`, and the loop body mirrors
``repro.core.gus._gus_body`` — integer assignments from this kernel must
equal the jitted XLA path and the NumPy oracle *exactly*
(``tests/test_gus_parity.py`` is the three-way harness).

This module depends only on jax — never on ``repro.core`` (the core's GUS
module imports *us*, and a reverse import would cycle).  Whether the
kernel is compiled or interpreted follows :func:`pallas_interpret`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gus_assign_pallas", "pallas_interpret", "lane_layout"]

#: matches ``repro.core.gus.NEG`` — the masked-out candidate score.  The
#: parity bar requires the identical sentinel: a served/dropped decision is
#: ``score > NEG`` in both implementations.
NEG = -1e30


def pallas_interpret() -> bool:
    """Whether this process runs the Pallas kernels in interpret mode.

    Decided by the platform that unplaced arrays live on
    (``jax.default_backend()``): compiled Mosaic on a TPU, interpret mode
    (plain jax ops) on the CPU.  Any other platform has neither path and
    raises rather than falling back.
    """
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas scheduler kernels run on 'tpu' (compiled) or 'cpu' "
        f"(interpret mode), not on {platform!r}; use backend='xla'"
    )


def lane_layout(x, n_servers: int, n_variants: int):
    """``(B, M)`` per-server vector -> ``(B, 1, M * L)`` lane-repeated row,
    plus the ``(1, M * L)`` int32 lane -> server map."""
    srv = jnp.repeat(jnp.arange(n_servers, dtype=jnp.int32), n_variants)[None]
    rep = jnp.repeat(x.astype(jnp.float32), n_variants, axis=-1)[:, None, :]
    return rep, srv


def _lane_pick(sel, x, fill):
    """The value of ``x`` at the one lane where ``sel`` holds (exact)."""
    return jnp.max(jnp.where(sel, x, fill))


def _gus_kernel(
    cover_ref, A_ref, C_ref, wa_ref, wc_ref, scal_ref,
    srv_ref, acc_ref, ctime_ref, v_ref, u_ref, avail_ref, gamma_ref, eta_ref,
    j_ref, l_ref,
    *, n_requests: int, n_variants: int,
):
    srv = srv_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, srv.shape, 1)
    n_lanes = srv.shape[1]
    max_as = scal_ref[0, 0]
    max_cs = scal_ref[0, 1]

    def body(i, state):
        gamma, eta = state
        s_i = cover_ref[0, i]
        A = A_ref[0, i]
        C = C_ref[0, i]
        row = pl.ds(i, 1)
        acc = acc_ref[row, :]
        ctime = ctime_ref[row, :]
        row_v = v_ref[row, :]
        row_u = u_ref[row, :]

        # --- fused utility + feasibility (us_tensor / hard_feasible, op-for-op)
        acc_term = (acc - A) / max_as
        time_term = (C - ctime) / max_cs
        row_us = wa_ref[0, i] * acc_term + wc_ref[0, i] * time_term
        feas = (avail_ref[row, :] != 0.0) & (acc >= A) & (ctime <= C)

        # --- one step of Algorithm 1 (mirrors repro.core.gus._gus_body) ----
        is_local = srv == s_i
        eta_s = _lane_pick(is_local, eta, -jnp.inf)
        ok = feas & (row_v <= gamma) & (is_local | (row_u <= eta_s))
        score = jnp.where(ok, row_us, NEG)
        best = jnp.max(score)
        flat = jnp.min(jnp.where(score == best, lane, n_lanes))
        served = best > NEG
        sel = lane == flat
        j = _lane_pick(sel, srv, -1)
        vv = _lane_pick(sel, row_v, -jnp.inf)
        uv = _lane_pick(sel, row_u, -jnp.inf)

        offload = served & (j != s_i)
        gamma = jnp.where(srv == j, gamma + jnp.where(served, -vv, 0.0), gamma)
        eta = jnp.where(is_local, eta + jnp.where(offload, -uv, 0.0), eta)
        j_ref[0, i] = jnp.where(served, j, -1)
        l_ref[0, i] = jnp.where(served, flat - j * n_variants, -1)
        return gamma, eta

    jax.lax.fori_loop(0, n_requests, body, (gamma_ref[...], eta_ref[...]))


def gus_assign_pallas(
    cover, A, C, w_a, w_c, acc, ctime, v, u, avail, gamma, eta,
    max_as, max_cs, *, interpret=None,
):
    """Run the fused GUS kernel on a batch of frames.

    Shapes (leading batch axis ``B`` required; ``repro.core.gus`` adds it
    for single frames): ``cover/A/C/w_a/w_c`` ``(B, N)``;
    ``acc/ctime/v/u/avail`` ``(B, N, M, L)``; ``gamma/eta`` ``(B, M)``;
    ``max_as/max_cs`` ``(B,)``.  Returns ``(j, l)`` int32 ``(B, N)`` arrays
    with ``-1`` encoding *drop*.  ``interpret=None`` resolves via
    :func:`pallas_interpret`.
    """
    if interpret is None:
        interpret = pallas_interpret()
    B, N, M, L = acc.shape
    if N == 0:
        empty = jnp.full((B, 0), -1, jnp.int32)
        return empty, empty
    K = M * L
    scal = jnp.stack(
        [jnp.broadcast_to(max_as, (B,)), jnp.broadcast_to(max_cs, (B,))], axis=-1
    ).astype(jnp.float32)
    gamma_x, srv = lane_layout(gamma, M, L)
    eta_x, _ = lane_layout(eta, M, L)

    def smem(n):  # (B, 1, n): a block's last two axes must be whole
        return pl.BlockSpec((None, 1, n), lambda b: (b, 0, 0),
                            memory_space=pltpu.SMEM)

    def vmem(*shape):
        return pl.BlockSpec((None, *shape), lambda b: (b,) + (0,) * len(shape))

    cand = vmem(N, K)
    row = vmem(1, K)
    out_j, out_l = pl.pallas_call(
        functools.partial(_gus_kernel, n_requests=N, n_variants=L),
        grid=(B,),
        in_specs=[smem(N)] * 5 + [smem(2)]
        + [pl.BlockSpec((1, K), lambda b: (0, 0))]
        + [cand] * 5 + [row, row],
        out_specs=[smem(N), smem(N)],
        out_shape=[jax.ShapeDtypeStruct((B, 1, N), jnp.int32)] * 2,
        interpret=interpret,
    )(
        cover.astype(jnp.int32)[:, None],
        *(x.astype(jnp.float32)[:, None] for x in (A, C, w_a, w_c, scal)),
        srv,
        *(x.astype(jnp.float32).reshape(B, N, K)
          for x in (acc, ctime, v, u, avail)),
        gamma_x,
        eta_x,
    )
    return out_j[:, 0], out_l[:, 0]
