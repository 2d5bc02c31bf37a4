"""User-Satisfaction (US) metric — Eq. (1) of the paper.

US_{ijkl} = w_a * (a_{ijkl} - A_i) / Max_as  +  w_c * (C_i - c_{ijkl}) / Max_cs

A request is *satisfiable* by (j, l) iff the accuracy floor and the deadline
hold AND the variant is placed on j (constraints 2b, 2c and placement).
"""
from __future__ import annotations

import jax.numpy as jnp

from .instance import FlatInstance

__all__ = ["us_tensor", "hard_feasible", "mean_us", "satisfied_mask", "SATISFACTION_LEAVES"]

#: the ``FlatInstance`` leaves :func:`satisfied_mask` and :func:`mean_us`
#: read; a caller that uploads their inputs itself uploads these
SATISFACTION_LEAVES = ("acc", "ctime", "A", "C", "w_a", "w_c", "max_as", "max_cs")


def us_tensor(inst: FlatInstance) -> jnp.ndarray:
    """(..., N, M, L) user satisfaction for every candidate assignment."""
    max_as = inst.max_as[..., None, None, None]  # broadcast over (N, M, L)
    max_cs = inst.max_cs[..., None, None, None]
    acc_term = (inst.acc - inst.A[..., :, None, None]) / max_as
    time_term = (inst.C[..., :, None, None] - inst.ctime) / max_cs
    return (
        inst.w_a[..., :, None, None] * acc_term
        + inst.w_c[..., :, None, None] * time_term
    )


def hard_feasible(inst: FlatInstance) -> jnp.ndarray:
    """(..., N, M, L) bool: placement + accuracy floor + deadline (2b), (2c)."""
    return (
        inst.avail
        & (inst.acc >= inst.A[..., :, None, None])
        & (inst.ctime <= inst.C[..., :, None, None])
    )


def satisfied_mask(inst: FlatInstance, assign_j, assign_l) -> jnp.ndarray:
    """(..., N) bool: request i assigned (assign_j >= 0) and QoS met."""
    served = assign_j >= 0
    j = jnp.maximum(assign_j, 0)
    l = jnp.maximum(assign_l, 0)
    idx_n = jnp.arange(assign_j.shape[-1])
    acc = jnp.take_along_axis(
        jnp.take_along_axis(inst.acc, j[..., :, None, None], axis=-2)[..., :, 0, :],
        l[..., :, None],
        axis=-1,
    )[..., :, 0]
    ct = jnp.take_along_axis(
        jnp.take_along_axis(inst.ctime, j[..., :, None, None], axis=-2)[..., :, 0, :],
        l[..., :, None],
        axis=-1,
    )[..., :, 0]
    del idx_n
    return served & (acc >= inst.A) & (ct <= inst.C)


def mean_us(inst: FlatInstance, assign_j, assign_l) -> jnp.ndarray:
    """Objective (2): mean US over all |N| requests (dropped contribute 0).

    Gathers the chosen (j, l) cell of ``acc``/``ctime`` first and evaluates
    Eq. (1) only there — the same elementwise operations, in the same order,
    on the same operands as picking out of the full :func:`us_tensor`, so
    the result is bit-identical while doing ~M*L times less arithmetic
    (this sits on the fleet's per-window metrics path).
    """
    served = assign_j >= 0
    j = jnp.maximum(assign_j, 0)
    l = jnp.maximum(assign_l, 0)

    def pick(x):
        return jnp.take_along_axis(
            jnp.take_along_axis(x, j[..., :, None, None], axis=-2)[..., :, 0, :],
            l[..., :, None],
            axis=-1,
        )[..., :, 0]

    acc_term = (pick(inst.acc) - inst.A) / inst.max_as[..., None]
    time_term = (inst.C - pick(inst.ctime)) / inst.max_cs[..., None]
    picked = inst.w_a * acc_term + inst.w_c * time_term
    return jnp.where(served, picked, 0.0).mean(axis=-1)
