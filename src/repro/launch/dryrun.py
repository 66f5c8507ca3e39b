import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
# ^ MUST be the first two lines, before ANY other import (jax locks the
#   device count on first init).  Set here only — smoke tests and benches
#   must keep seeing 1 device.

# Multi-pod dry-run (deliverable e).
# For every (architecture × input shape × mesh) combination:
#   jax.jit(step, in_shardings, out_shardings).lower(**input_specs).compile()
# must succeed; we record memory_analysis(), cost_analysis(), and the
# collective bytes parsed from the post-SPMD optimized HLO into a JSON
# artifact consumed by the roofline analysis (benchmarks/roofline.py).
#
# Usage:
#   PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b \
#       --shape train_4k [--multi-pod]
#   PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] \
#       [--out artifacts/dryrun]
import argparse
import json
import re
import time
import traceback

import jax

from .. import configs
from ..configs.shapes import SHAPES
from .mesh import make_production_mesh
from .specs import input_specs

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _bytes_of_shape(text: str) -> int:
    """Parse 'bf16[8,128]' (or tuple '(f32[..], u32[..])') → total bytes."""
    total = 0
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum result bytes of every collective op in post-SPMD optimized HLO.

    Per-device program ⇒ per-device bytes.  ``*-start`` / ``*-done`` pairs
    (async collectives) are counted once via the -start op.
    """
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        if "=" not in s:
            continue
        # '%name = TYPE op(...)' where TYPE is 'bf16[..]' or a tuple '(f32[..], ..)'
        m = re.match(r"^[^=]*=\s*((?:\([^)]*\)|\S+))\s+([a-z-]+)\(", s)
        if not m:
            continue
        shape_txt, op = m.group(1), m.group(2)
        base = op[:-6] if op.endswith("-start") else op
        if base in _COLLECTIVES and not op.endswith("-done"):
            out[base] += _bytes_of_shape(shape_txt)
            counts[base] += 1
    return {"bytes": out, "counts": counts,
            "total_bytes": sum(out.values())}


def _compile_metrics(spec, mesh) -> dict:
    """lower+compile a ProgramSpec; return {flops, bytes, collectives}."""
    with jax.set_mesh(mesh):
        compiled = jax.jit(spec.fn, in_shardings=spec.in_shardings,
                           out_shardings=spec.out_shardings
                           ).lower(*spec.args).compile()
    cost = compiled.cost_analysis() or {}
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "collectives": collective_bytes(compiled.as_text())}


def cost_probes(arch: str, shape_name: str, mesh, mode: str) -> dict:
    """1- and 2-super-block unrolled probes (see models/costmode.py):
    total-per-device metric M(R) = M1 + (R−1)·(M2 − M1)."""
    import dataclasses

    from .. import configs as _configs
    from ..models.costmode import cost_probe
    cfg = _configs.get(arch, SHAPES[shape_name])
    sb = len(cfg.mixer_pattern)
    out = {"n_repeats": cfg.n_repeats, "superblock": sb}
    with cost_probe():
        for tag, layers in (("m1", sb), ("m2", 2 * sb)):
            c = dataclasses.replace(cfg, n_layers=layers)
            spec = input_specs(arch, shape_name, mesh, cfg_override=c,
                               mode_override=None if mode == "-" else mode)
            out[tag] = _compile_metrics(spec, mesh)
    r = cfg.n_repeats
    coll1, coll2 = out["m1"]["collectives"], out["m2"]["collectives"]
    out["total"] = {
        "flops": out["m1"]["flops"]
        + (r - 1) * (out["m2"]["flops"] - out["m1"]["flops"]),
        "bytes": out["m1"]["bytes"]
        + (r - 1) * (out["m2"]["bytes"] - out["m1"]["bytes"]),
        "collective_bytes": (coll1["total_bytes"]
                             + (r - 1) * (coll2["total_bytes"]
                                          - coll1["total_bytes"])),
        "collective_bytes_by_kind": {
            k: coll1["bytes"][k] + (r - 1) * (coll2["bytes"][k]
                                              - coll1["bytes"][k])
            for k in coll1["bytes"]},
    }
    return out


def run_one(arch: str, shape_name: str, multi_pod: bool,
            verbose: bool = True, probe: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    spec = input_specs(arch, shape_name, mesh)
    rec: dict = {"arch": arch, "shape": shape_name,
                 "mesh": "2x16x16" if multi_pod else "16x16",
                 "devices": int(mesh.devices.size), "status": "ok",
                 "kind": spec.meta["kind"],
                 "mode": spec.meta.get("mode", "-")}
    try:
        donate = (0,) if spec.meta["kind"] == "train" else ()
        with jax.set_mesh(mesh):
            jitted = jax.jit(spec.fn, in_shardings=spec.in_shardings,
                             out_shardings=spec.out_shardings,
                             donate_argnums=donate)
            lowered = jitted.lower(*spec.args)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):  # pre-0.5 jax: list of one dict
            cost = cost[0] if cost else {}
        hlo = compiled.as_text()
        coll = collective_bytes(hlo)
        rec.update({
            "lower_s": round(t1 - t0, 2), "compile_s": round(t2 - t1, 2),
            "memory_analysis": {
                k: int(getattr(mem, k)) for k in (
                    "argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes", "generated_code_size_in_bytes")
                if hasattr(mem, k)},
            "cost_analysis": {k: float(v) for k, v in (cost or {}).items()
                              if isinstance(v, (int, float))},
            "collectives": coll,
            "hlo_ops": len(hlo.splitlines()),
        })
        if probe:
            # 1- & 2-super-block unrolled cost probes for exact roofline
            # totals (scan bodies are counted once by HLO cost analysis)
            rec["cost_probe"] = cost_probes(arch, shape_name, mesh,
                                            rec["mode"])
        if verbose:
            print(f"[dryrun] {arch} × {shape_name} × {rec['mesh']}: OK "
                  f"(lower {rec['lower_s']}s, compile {rec['compile_s']}s)")
            print("  memory_analysis:", rec["memory_analysis"])
            fl = rec["cost_analysis"].get("flops", 0)
            print(f"  cost_analysis: flops/device={fl:.3e} "
                  f"bytes={rec['cost_analysis'].get('bytes accessed', 0):.3e}")
            print("  collectives:", coll["counts"], "→",
                  f"{coll['total_bytes']/1e6:.1f} MB/device")
    except Exception as e:  # noqa: BLE001 — record failures as data
        rec["status"] = "fail"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        if verbose:
            print(f"[dryrun] {arch} × {shape_name} × {rec['mesh']}: FAIL "
                  f"{rec['error']}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--no-probe", action="store_true")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    archs = configs.names() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]

    results = []
    for arch in archs:
        for shape in shapes:
            tag = f"{arch}_{shape}_{'2x16x16' if args.multi_pod else '16x16'}"
            path = os.path.join(args.out, tag.replace("/", "-") + ".json")
            if args.skip_existing and os.path.exists(path):
                with open(path) as f:
                    prev = json.load(f)
                if prev.get("status") == "ok":
                    results.append(prev)
                    print(f"[dryrun] {arch} × {shape}: cached OK")
                    continue
            rec = run_one(arch, shape, args.multi_pod,
                          probe=not args.no_probe)
            results.append(rec)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
    ok = sum(r["status"] == "ok" for r in results)
    print(f"[dryrun] {ok}/{len(results)} combinations lowered+compiled")
    if ok < len(results):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
