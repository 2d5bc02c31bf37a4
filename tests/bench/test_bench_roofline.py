"""The roofline's peak table and the work counts from shapes."""
from __future__ import annotations

import numpy as np
import pytest

from bench import roofline
from bench.gen.instance import FIELDS, generate_instance


def test_unknown_device_kind_raises():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
    with pytest.raises(KeyError):
        roofline.share_pct({"bytes": 1, "flops": 1}, 1.0, "TPU v9 imaginary")


def _dtypes(leaves):
    return {k: np.asarray(v).dtype for k, v in leaves.items()}


def test_gus_count_is_the_same_for_xla_and_pallas_inputs():
    """The XLA loop takes the instance as a batch of frames; the Pallas
    kernel takes the same leaves with the (M, L) grid on lanes.  The count
    reads only shapes and dtypes, so both are held to one number."""
    from repro.core import FlatInstance, pad_instance, stack_instances

    insts = [FlatInstance(**{k: f[k] for k in FIELDS})
             for f in (generate_instance(s) for s in (1, 2))]
    batch = stack_instances([pad_instance(i, 128) for i in insts])
    N, M, L = insts[0].acc.shape
    xla = {k: np.asarray(getattr(batch, k)) for k in FIELDS}
    pallas = {k: (v.reshape(v.shape[0], v.shape[1], M * L) if v.ndim == 4 else v)
              for k, v in xla.items()}
    a = roofline.gus_work(_dtypes(xla), 2 * N, 2, M, L)
    b = roofline.gus_work(_dtypes(pallas), 2 * N, 2, M, L)
    assert a == b and a["bytes"] > 0


def test_padding_does_not_raise_the_count():
    from repro.core import FlatInstance, pad_instance

    inst = FlatInstance(**{k: v for k, v in generate_instance(3).items()})
    N, M, L = inst.acc.shape
    padded = pad_instance(inst, 256)
    real = roofline.gus_work(_dtypes({k: getattr(inst, k) for k in FIELDS}), N, 1, M, L)
    pad = roofline.gus_work(_dtypes({k: getattr(padded, k) for k in FIELDS}), N, 1, M, L)
    assert real == pad
    assert roofline.gus_work(_dtypes({k: getattr(padded, k) for k in FIELDS}),
                             256, 1, M, L)["bytes"] > real["bytes"]


F32_LEAVES = dict(acc="float32", ctime="float32", v="float32", u="float32", avail="bool",
                  cover="int32", A="float32", C="float32", w_a="float32", w_c="float32",
                  gamma="float32", eta="float32")


@pytest.mark.parametrize("dtypes", [
    F32_LEAVES,
    {**F32_LEAVES, **{k: "bfloat16" for k in ("acc", "ctime", "v", "u", "A", "C")}},
], ids=["float32", "bfloat16"])
def test_count_is_linear_in_real_rows_and_share_is_bounded(dtypes):
    import ml_dtypes  # noqa: F401  (registers bfloat16 with NumPy)

    one = roofline.gus_work(dtypes, 1000, 10, 10, 10)
    two = roofline.gus_work(dtypes, 2000, 10, 10, 10)
    frames = roofline.gus_work(dtypes, 0, 10, 10, 10)
    assert two["bytes"] - frames["bytes"] == 2 * (one["bytes"] - frames["bytes"])
    least = one["bytes"] / roofline.PEAKS["TPU v5 lite"]["hbm_bytes_per_s"]
    assert roofline.share_pct(one, least, "TPU v5 lite") == pytest.approx(100.0)
    assert roofline.share_pct(one, 0.0, "TPU v5 lite") is None
