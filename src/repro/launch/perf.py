import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "10")
"""Perf-iteration harness (§Perf): lower one (arch x shape) under a NAMED
experiment variant (sharding-rule override and/or config tweak), emit the
three roofline terms, and diff against the baseline report.

Each experiment encodes one hypothesis from EXPERIMENTS.md §Perf.

Usage:
  PYTHONPATH=src python -m repro.launch.perf --arch yi-9b --shape decode_32k \
      --variant kvseq_model
"""
import argparse
import dataclasses
import sys
import time

from ..compile_cache import use_compile_cache
from ..configs import ARCH_IDS, get_config
from ..sharding import DEFAULT_RULES
from .dryrun import lower_one
from .specs import SHAPES

# ---------------------------------------------------------------------------
# experiment variants: name -> dict(rules=..., cfg_patch=..., note=...)
# ---------------------------------------------------------------------------

VARIANTS = {
    "baseline": dict(rules=None, cfg_patch={}, note="paper-faithful baseline"),
    # decode: shard the KV-cache sequence axis over `model` when kv_heads
    # cannot be sharded (GQA kv < mesh) — turns a replicated multi-GB cache
    # into 1/16 per chip; softmax over the sharded axis costs one tiny
    # all-reduce of (B,H) stats instead of replicated reads.
    "kvseq_model": dict(
        rules={"kv_seq": "model"},
        cfg_patch={},
        note="decode KV cache sharded over model on the sequence axis",
    ),
    # decode: ALSO pull the final logits all-gather out: vocab stays sharded
    # and only the (B,1) argmax index is exchanged.
    # long-context decode (batch=1): the data axis is idle; shard the cache
    # sequence over BOTH axes -> 256-way context parallelism for the cache
    "kvseq_2d": dict(
        rules={"kv_seq": ("data", "model")},
        cfg_patch={},
        note="cache seq sharded over data+model (256-way context parallel)",
    ),
    # ssm: 24 heads cannot shard a 16-way axis (replicated); shard the
    # headdim channels instead (64 % 16 == 0)
    "ssm_headdim_model": dict(
        rules={"ssm_headdim": "model", "ssm_heads": None},
        cfg_patch={},
        note="shard SSD head channels instead of (non-dividing) heads",
    ),
    # decode: int8-quantized KV cache (per-token-per-head scales) halves the
    # cache byte stream vs bf16 on top of kv_seq sharding
    "kvseq_int8": dict(
        rules={"kv_seq": "model"},
        cfg_patch={"kv_cache_dtype": "int8"},
        note="kv_seq sharding + int8 KV cache",
    ),
    "kvseq_localtopk": dict(
        rules={"kv_seq": "model"},
        cfg_patch={"local_argmax": True},
        note="kv_seq sharding + distributed argmax (no logits all-gather)",
    ),
    # train/prefill: flash-style chunked attention — never materializes the
    # (S,T) f32 score tensor (the baseline's dominant HBM term) and statically
    # slices the causal/windowed k-range (~2x fewer score FLOPs)
    "attn_chunked": dict(
        rules=None, cfg_patch={"attn_impl": "chunked"},
        note="chunked flash-style attention, causal k-slicing",
    ),
    "attn_chunked_kvseq": dict(
        rules={"kv_seq": "model"}, cfg_patch={"attn_impl": "chunked"},
        note="chunked attention + kv_seq sharding",
    ),
    # train: activation-checkpoint the scanned block
    "remat_on": dict(rules=None, cfg_patch={"remat": True}, note="remat scanned block"),
    "remat_off": dict(rules=None, cfg_patch={"remat": False}, note="no remat"),
    # moe: when n_experts cannot divide the mesh (qwen2-moe: 60 on 16), the
    # (E, C, D) expert activations replicate; shard the CAPACITY dim instead
    "moe_capacity_sharded": dict(
        rules={"capacity": "model", "experts": None},
        cfg_patch={"attn_impl": "chunked"},
        note="expert activations sharded on capacity (experts replicated)",
    ),
    # moe: int16 routing intermediates in the dispatch path
    "moe_small_dispatch": dict(
        rules=None,
        cfg_patch={"moe_dispatch_dtype": "int16"},
        note="MoE dispatch one-hot/cumsum in int16 instead of int32",
    ),
    # moe: lower capacity factor (less dispatch traffic, more drops)
    "moe_cf1": dict(rules=None, cfg_patch={"capacity_factor": 1.0}, note="capacity factor 1.0"),
    # combined best-known for MoE training
    "moe_best": dict(
        rules=None,
        cfg_patch={"attn_impl": "chunked", "capacity_factor": 1.0},
        note="chunked attention + capacity 1.0",
    ),
    "attn_chunked_noremat": dict(
        rules=None, cfg_patch={"attn_impl": "chunked", "remat": False},
        note="chunked attention, remat off (bytes vs residency trade)",
    ),
    # selective remat: keep matmul outputs, recompute only elementwise chain —
    # most of remat-off's byte/flop win at a fraction of the residency cost
    "attn_chunked_remat_dots": dict(
        rules=None, cfg_patch={"attn_impl": "chunked", "remat_policy": "dots"},
        note="chunked attention + dots-saveable remat policy",
    ),
    # serve without FSDP is the default; this measures the (bad) train-rules
    # alternative to quantify why SERVE_RULES exists
    "serve_with_train_rules": dict(
        rules={"embed": "data"}, cfg_patch={}, note="FSDP rules in decode (ablation)"
    ),
}


def run_variant(arch: str, shape: str, variant: str, out_dir: str = "reports/perf"):
    spec = VARIANTS[variant]
    cfg_patch = dict(spec["cfg_patch"])
    rules = dict(DEFAULT_RULES, **(spec["rules"] or {})) if spec["rules"] else None

    # config patches that are real ModelConfig fields get applied via replace;
    # feature flags (local_argmax, moe_dispatch_dtype) are module-level toggles
    import repro.models.moe as moe_mod
    import repro.serving.engine as eng_mod

    from repro.configs.base import ModelConfig

    base_cfg = get_config(arch)
    field_names = {f.name for f in dataclasses.fields(ModelConfig)}
    cfg_fields = {k: v for k, v in cfg_patch.items() if k in field_names}
    flags = {k: v for k, v in cfg_patch.items() if k not in field_names}

    old_dispatch = getattr(moe_mod, "DISPATCH_DTYPE", None)
    old_argmax = getattr(eng_mod, "LOCAL_ARGMAX", None)
    if "moe_dispatch_dtype" in flags:
        moe_mod.DISPATCH_DTYPE = flags["moe_dispatch_dtype"]
    if "local_argmax" in flags:
        eng_mod.LOCAL_ARGMAX = bool(flags["local_argmax"])

    try:
        t0 = time.time()
        _, compiled, report = lower_one(
            arch, shape, rules=rules, loop_correct=True, cfg_patch=cfg_fields or None
        )
        dt = time.time() - t0
    finally:
        if old_dispatch is not None or "moe_dispatch_dtype" in flags:
            moe_mod.DISPATCH_DTYPE = old_dispatch or "int32"
        if old_argmax is not None or "local_argmax" in flags:
            eng_mod.LOCAL_ARGMAX = bool(old_argmax)

    os.makedirs(out_dir, exist_ok=True)
    tag = f"{arch}__{shape}__{variant}"
    report.save(os.path.join(out_dir, tag + ".json"))
    print(f"[{variant:24s} {dt:6.1f}s] {report.row()}")
    return report


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--shape", choices=list(SHAPES), required=True)
    ap.add_argument("--variant", choices=list(VARIANTS), action="append", required=True)
    ap.add_argument("--out", default="reports/perf")
    args = ap.parse_args(argv)
    use_compile_cache()
    for v in args.variant:
        run_variant(args.arch, args.shape, v, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
