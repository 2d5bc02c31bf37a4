import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "10")
"""Multi-pod dry-run (deliverable (e)).

Lowers + compiles every (architecture × input shape) on the production meshes
(16x16 single-pod and 2x16x16 multi-pod) with ShapeDtypeStruct inputs — no
device allocation — and records memory_analysis / cost_analysis / collective
bytes for the roofline table.

The two lines above MUST run before any other import: jax locks the device
count at first init, and the dry-run needs 512 placeholder host devices.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out reports/dryrun]
"""
import argparse
import sys
import time
import traceback


from ..compile_cache import use_compile_cache
from ..configs import ARCH_IDS, get_config
from ..models.model import Model
from ..roofline import roofline_terms
from .mesh import make_production_mesh, mesh_name
from .specs import SHAPES, model_flops, shape_config
from .steps import build_prefill_step, build_serve_step, build_train_step


def _compile(cfg, shape, mesh, rules):
    model = Model(cfg)
    if shape.kind == "train":
        fn, args = build_train_step(model, mesh, shape, rules=rules)
    elif shape.kind == "prefill":
        fn, args = build_prefill_step(model, mesh, shape, rules=rules)
    else:
        fn, args = build_serve_step(model, mesh, shape, rules=rules)
    with mesh:
        lowered = fn.lower(*args)
        compiled = lowered.compile()
    return lowered, compiled


def _counts(compiled):
    from ..roofline import counts_from_artifacts

    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    return counts_from_artifacts(cost, compiled.as_text()), cost


def _loop_corrected_counts(cfg, shape, mesh, rules):
    """XLA:CPU cost_analysis counts lax.scan bodies once.  For scanned-layer
    models, compile UNROLLED 1-layer and 2-layer variants (cheap) and
    extrapolate:  total(L) = base + L * body  with  body = c2 - c1."""
    import dataclasses as dc

    def small(k):
        kw = dict(num_layers=k, scan_layers=False)
        if cfg.family == "encdec":
            kw["num_enc_layers"] = k
        return dc.replace(cfg, **kw)

    out = {}
    per_kind = {}
    c = {}
    for k in (1, 2):
        _, comp = _compile(small(k), shape, mesh, rules)
        c[k], _ = _counts(comp)
        del comp
    L = cfg.num_layers
    for key in ("flops", "bytes", "coll"):
        body = max(c[2][key] - c[1][key], 0.0)
        base = max(c[1][key] - body, 0.0)
        out[key] = base + L * body
    for kind in c[1]["coll_breakdown"]:
        body = max(c[2]["coll_breakdown"][kind] - c[1]["coll_breakdown"][kind], 0)
        base = max(c[1]["coll_breakdown"][kind] - body, 0)
        per_kind[kind] = base + L * body
    out["coll_breakdown"] = per_kind
    out["coll"] = float(sum(per_kind.values()))
    return out


def lower_one(arch: str, shape_name: str, *, multi_pod: bool = False, rules=None,
              loop_correct: bool = True, cfg_patch=None, opt: bool = False):
    """Returns (lowered, compiled, report) for one combination.

    ``cfg_patch`` (perf experiments) is applied AFTER shape_config so it wins
    over per-shape defaults like auto-remat.  ``opt`` applies the beyond-paper
    recommended settings found in §Perf: chunked flash-style attention +
    dots-saveable remat for train/prefill, kv_seq->model cache sharding for
    decode."""
    import dataclasses as _dc

    shape = SHAPES[shape_name]
    cfg = shape_config(get_config(arch), shape)
    if opt:
        if shape.kind in ("train", "prefill"):
            cfg = _dc.replace(cfg, attn_impl="chunked", remat_policy="dots")
        elif cfg.num_kv_heads % 16 != 0:
            # kv_seq sharding pays off ONLY when kv_heads cannot shard the
            # 16-way model axis (else it trades away head locality — measured
            # 3-10x regressions on kv=16 archs, see §Perf)
            from ..sharding import DEFAULT_RULES

            rules = dict(DEFAULT_RULES, kv_seq="model", **(rules or {}))
    if cfg_patch:
        cfg = _dc.replace(cfg, **cfg_patch)
    mesh = make_production_mesh(multi_pod=multi_pod)

    lowered, compiled = _compile(cfg, shape, mesh, rules)

    try:
        mem = compiled.memory_analysis()
        mem_str = str(mem)
    except Exception as e:  # CPU backend may not implement it
        mem_str = f"unavailable ({e})"
    raw_counts, cost = _counts(compiled)

    corrected = None
    if loop_correct and cfg.scan_layers:
        corrected = _loop_corrected_counts(cfg, shape, mesh, rules)
        # never report less than the raw artifact
        for key in ("flops", "bytes", "coll"):
            corrected[key] = max(corrected[key], raw_counts[key])

    report = roofline_terms(
        arch=arch,
        shape=shape_name,
        mesh_name=mesh_name(mesh),
        n_devices=mesh.devices.size,
        cost_analysis=cost,
        hlo_text=compiled.as_text(),
        model_flops_total=model_flops(cfg, shape),
        memory_analysis=mem_str,
        corrected_counts=corrected,
    )
    return lowered, compiled, report


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS + ["all"], default="all")
    ap.add_argument("--shape", choices=list(SHAPES) + ["all"], default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="reports/dryrun")
    ap.add_argument("--continue-on-error", action="store_true")
    ap.add_argument("--opt", action="store_true",
                    help="apply the beyond-paper optimized settings (§Perf)")
    ap.add_argument(
        "--no-loop-correct", dest="loop_correct", action="store_false",
        help="skip the 1/2-layer extrapolation fixing XLA:CPU's scan-body "
             "flop undercount (use for multi-pod lowering-only passes)",
    )
    args = ap.parse_args(argv)
    use_compile_cache()

    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    os.makedirs(args.out, exist_ok=True)

    n_fail = 0
    for arch in archs:
        for shape in shapes:
            tag = f"{arch}__{shape}__{'2x16x16' if args.multi_pod else '16x16'}"
            if args.opt:
                tag += "__opt"
            t0 = time.time()
            try:
                _, compiled, report = lower_one(
                    arch, shape, multi_pod=args.multi_pod,
                    loop_correct=args.loop_correct, opt=args.opt,
                )
                if args.opt:
                    report.mesh += "+opt"
                report.save(os.path.join(args.out, tag + ".json"))
                print(f"[OK {time.time()-t0:6.1f}s] {report.row()}", flush=True)
                del compiled
            except Exception:
                n_fail += 1
                print(f"[FAIL {time.time()-t0:6.1f}s] {tag}", flush=True)
                traceback.print_exc()
                if not args.continue_on_error:
                    return 1
    print(f"done: {len(archs)*len(shapes)-n_fail} ok, {n_fail} failed")
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
