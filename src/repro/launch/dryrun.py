import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x input-shape) on the
production meshes, and extract the roofline terms from the compiled artifact.

MUST be run as its own process (``python -m repro.launch.dryrun``): the
lines above execute before any other import so the 512 placeholder CPU
devices exist before jax locks the device count, and the run stays on the
CPU platform even on a machine with an accelerator attached.  Nothing is allocated —
inputs are ShapeDtypeStructs.

Per combo it records (EXPERIMENTS.md §Dry-run/§Roofline):
  * memory_analysis (per-device argument/output/temp bytes),
  * cost_analysis FLOPs / bytes accessed (per-device),
  * per-device collective traffic parsed from the post-SPMD HLO,
  * the three roofline terms + dominant bottleneck,
  * MODEL_FLOPS = 6*N*D (active N for MoE) and the useful-compute ratio.
"""
import argparse
import json
import re
import time
import traceback

import jax
import numpy as np

from repro.configs import SKIPS, dryrun_pairs, get_config, get_shape
from repro.energy import costs as energy_costs
from repro.launch import mesh as mesh_lib
from repro.launch.steps import build_step

# per-device traffic multiplier per collective kind (ring-algorithm bytes that
# cross this device's links, as a fraction of the printed result size)
_COLL_WEIGHTS = {
    "all-reduce": 2.0,        # reduce-scatter + all-gather
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(?:\(?)([a-z0-9\[\],{} ]*?)\)?\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", re.I)


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo_text: str) -> dict:
    """Sum per-device collective traffic from post-SPMD HLO text."""
    out = {k: {"count": 0, "bytes": 0.0} for k in _COLL_WEIGHTS}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        shapes_txt, kind = m.group(1), m.group(2).lower()
        if "-done" in line:
            continue  # async pair: count only the -start
        size = _shape_bytes(shapes_txt)
        out[kind]["count"] += 1
        out[kind]["bytes"] += size * _COLL_WEIGHTS[kind]
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items()
                             if isinstance(v, dict))
    return out


def _measure(cfg, shape, mesh, *, local_steps=5, unroll=False):
    """Compile one variant and return np.array([flops, bytes, coll_bytes])
    (per-device)."""
    with mesh:
        bundle = build_step(cfg, shape, mesh, **(
            {"local_steps": local_steps, "unroll": unroll}
            if shape.kind == "train" else {}))
        compiled = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                           out_shardings=bundle.out_shardings
                           ).lower(*bundle.args).compile()
    ca = compiled.cost_analysis()
    coll = collective_stats(compiled.as_text())
    return np.array([float(ca.get("flops", 0.0)),
                     float(ca.get("bytes accessed", 0.0)),
                     float(coll["total_bytes"])])


def calibrated_cost(cfg, shape, mesh, local_steps: int = 5) -> dict:
    """Loop-corrected per-device cost vector.

    ``compiled.cost_analysis()`` counts while-loop (lax.scan) bodies ONCE, so
    the scanned production step under-reports FLOPs/bytes/collectives.  We
    exploit the step's known linear structure  cost(L, T) = base + T*(u +
    (L-1)*layer)  and solve it from 2-4 tiny fully-unrolled compiles
    (L in {1,2}, T in {1,2}); hybrid (3-layer blocks + tail) and enc-dec
    (two stacks) get their own probes.  Exact for the loop structure; small
    fusion differences between L=1/L=2 variants are noise we accept.
    """
    import dataclasses as dc

    def var(**kw):
        return dc.replace(cfg, scan_unroll=True, **kw)

    fam = cfg.family
    if shape.kind == "train":
        T = local_steps
        if fam == "hybrid":
            f31 = _measure(var(num_layers=3), shape, mesh, local_steps=1, unroll=True)
            f61 = _measure(var(num_layers=6), shape, mesh, local_steps=1, unroll=True)
            f41 = _measure(var(num_layers=4), shape, mesh, local_steps=1, unroll=True)
            f32 = _measure(var(num_layers=3), shape, mesh, local_steps=2, unroll=True)
            block, tail, u = f61 - f31, f41 - f31, f32 - f31
            base = f31 - u
            nb, nt = cfg.num_layers // 3, cfg.num_layers % 3
            vec = base + T * (u + (nb - 1) * block + nt * tail)
            probes = 4
        elif fam == "encdec":
            f111 = _measure(var(encoder_layers=1, num_layers=1), shape, mesh,
                            local_steps=1, unroll=True)
            f211 = _measure(var(encoder_layers=2, num_layers=1), shape, mesh,
                            local_steps=1, unroll=True)
            f121 = _measure(var(encoder_layers=1, num_layers=2), shape, mesh,
                            local_steps=1, unroll=True)
            f112 = _measure(var(encoder_layers=1, num_layers=1), shape, mesh,
                            local_steps=2, unroll=True)
            enc, dec, u = f211 - f111, f121 - f111, f112 - f111
            base = f111 - u
            vec = base + T * (u + (cfg.encoder_layers - 1) * enc
                              + (cfg.num_layers - 1) * dec)
            probes = 4
        else:
            f11 = _measure(var(num_layers=1), shape, mesh, local_steps=1, unroll=True)
            f21 = _measure(var(num_layers=2), shape, mesh, local_steps=1, unroll=True)
            f12 = _measure(var(num_layers=1), shape, mesh, local_steps=2, unroll=True)
            lay, u = f21 - f11, f12 - f11
            base = f11 - u
            vec = base + T * (u + (cfg.num_layers - 1) * lay)
            probes = 3
    else:
        if fam == "hybrid":
            f3 = _measure(var(num_layers=3), shape, mesh)
            f6 = _measure(var(num_layers=6), shape, mesh)
            f4 = _measure(var(num_layers=4), shape, mesh)
            block, tail = f6 - f3, f4 - f3
            nb, nt = cfg.num_layers // 3, cfg.num_layers % 3
            vec = (f3 - block) + nb * block + nt * tail
            probes = 3
        elif fam == "encdec":
            f11 = _measure(var(encoder_layers=1, num_layers=1), shape, mesh)
            f21 = _measure(var(encoder_layers=2, num_layers=1), shape, mesh)
            f12 = _measure(var(encoder_layers=1, num_layers=2), shape, mesh)
            enc, dec = f21 - f11, f12 - f11
            vec = (f11 - enc - dec) + cfg.encoder_layers * enc + cfg.num_layers * dec
            probes = 3
        else:
            # probe at L=2/L=4: single-layer probes can trigger a different
            # GSPMD partitioning choice (observed on 36-head starcoder2),
            # breaking the linear model; wider, multi-layer probes are stable
            f2 = _measure(var(num_layers=2), shape, mesh)
            f4 = _measure(var(num_layers=4), shape, mesh)
            lay = (f4 - f2) / 2.0
            vec = (f2 - 2 * lay) + cfg.num_layers * lay
            probes = 2
    vec = np.maximum(vec, 0.0)
    return {"flops_per_device": float(vec[0]),
            "bytes_per_device": float(vec[1]),
            "collective_bytes_per_device": float(vec[2]),
            "probes": probes}


def model_flops(cfg, shape, local_steps: int = 5) -> float:
    """6*N*D with D = tokens processed by the step (fwd+bwd baked into the 6;
    serving steps use 2*N*D)."""
    n = cfg.num_active_params()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len * local_steps
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def run_one(arch: str, shape_name: str, multi_pod: bool,
            local_steps: int = 5, extra_tag: str = "",
            calibrate: bool = True, cfg=None) -> dict:
    cfg = cfg or get_config(arch)
    shape = get_shape(shape_name)
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    with mesh:
        bundle = build_step(cfg, shape, mesh, **(
            {"local_steps": local_steps} if shape.kind == "train" else {}))
        jitted = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                         out_shardings=bundle.out_shardings)
        lowered = jitted.lower(*bundle.args)
        compiled = lowered.compile()
    t1 = time.time()

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_stats(hlo)

    if calibrate:
        cal = calibrated_cost(cfg, shape, mesh, local_steps)
        dev_flops = cal["flops_per_device"]
        dev_bytes = cal["bytes_per_device"]
        coll_bytes = cal["collective_bytes_per_device"]
    else:
        cal = None
        dev_flops = float(ca.get("flops", 0.0))
        dev_bytes = float(ca.get("bytes accessed", 0.0))
        coll_bytes = float(coll["total_bytes"])

    # roofline terms in seconds (global work / global capability ==
    # per-device work / per-device capability)
    t_compute = dev_flops / mesh_lib.PEAK_FLOPS_BF16
    t_memory = dev_bytes / mesh_lib.HBM_BW
    t_coll = coll_bytes / mesh_lib.ICI_BW
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)

    mf = model_flops(cfg, shape, local_steps)
    useful = mf / (dev_flops * chips) if dev_flops else 0.0

    return {
        "arch": arch,
        "shape": shape_name,
        "mesh": f"{'x'.join(str(mesh.shape[a]) for a in mesh.axis_names)}"
                f" ({','.join(mesh.axis_names)})",
        "multi_pod": multi_pod,
        "tag": extra_tag,
        "kind": shape.kind,
        "step_meta": bundle.meta,
        "overrides": extra_tag,
        "compile_s": round(t1 - t0, 2),
        "memory": {
            "argument_bytes_per_device": ma.argument_size_in_bytes,
            "output_bytes_per_device": ma.output_size_in_bytes,
            "temp_bytes_per_device": ma.temp_size_in_bytes,
            "total_bytes_per_device": (ma.argument_size_in_bytes
                                       + ma.output_size_in_bytes
                                       + ma.temp_size_in_bytes),
        },
        "cost": {"flops_per_device": dev_flops,
                 "bytes_per_device": dev_bytes,
                 "raw_scan_flops_per_device": float(ca.get("flops", 0.0)),
                 "raw_scan_bytes_per_device": float(ca.get("bytes accessed", 0.0)),
                 "loop_calibrated": cal is not None},
        "collectives": coll,
        "collective_bytes_per_device": coll_bytes,
        "roofline": {
            **{f"t_{k}_s": v for k, v in terms.items()},
            "dominant": dominant,
            "model_flops": mf,
            "hlo_flops_global": dev_flops * chips,
            "useful_compute_ratio": useful,
        },
        "params_analytic": cfg.num_params(),
        "params_active": cfg.num_active_params(),
        # nominal device joules for this workload (repro.energy cost model);
        # feeds DeviceCostModel.from_dryrun / battery-gated fleet simulation
        "energy": energy_costs.energy_record(
            dev_flops, cfg.num_active_params(),
            local_steps if shape.kind == "train" else 1),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--out", default="benchmarks/dryrun_results")
    ap.add_argument("--local-steps", type=int, default=5)
    ap.add_argument("--tag", default="")
    ap.add_argument("--no-calibrate", action="store_true",
                    help="skip the loop-calibration probes (raw scan costs)")
    ap.add_argument("--override", nargs="*", default=[],
                    help="config overrides key=value (hillclimb variants); "
                         "e.g. --override model_axis_role=dp micro_batches=8")
    args = ap.parse_args()

    def apply_overrides(cfg):
        import dataclasses as dc
        for kv in args.override:
            k, v = kv.split("=", 1)
            cur = getattr(cfg, k)
            if isinstance(cur, bool):
                v = v.lower() in ("1", "true", "yes")
            elif isinstance(cur, int):
                v = int(v)
            elif isinstance(cur, float):
                v = float(v)
            cfg = dc.replace(cfg, **{k: v})
        return cfg

    pairs = dryrun_pairs()
    if args.arch != "all":
        pairs = [(a, s) for a, s in pairs if a == args.arch]
    if args.shape != "all":
        pairs = [(a, s) for a, s in pairs if s == args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch, shape in pairs:
        for mp in meshes:
            name = f"{arch}__{shape}__{'multi' if mp else 'single'}"
            if args.tag:
                name += f"__{args.tag}"
            path = os.path.join(args.out, name + ".json")
            try:
                # roofline table is single-pod; multi-pod proves compile only
                rec = run_one(arch, shape, mp, args.local_steps, args.tag,
                              calibrate=not args.no_calibrate and not mp,
                              cfg=apply_overrides(get_config(arch)))
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                r = rec["roofline"]
                print(f"OK   {name}: compile={rec['compile_s']}s "
                      f"mem/dev={rec['memory']['total_bytes_per_device']/2**30:.2f}GiB "
                      f"t_comp={r['t_compute_s']:.3e} t_mem={r['t_memory_s']:.3e} "
                      f"t_coll={r['t_collective_s']:.3e} dom={r['dominant']} "
                      f"useful={r['useful_compute_ratio']:.2f}", flush=True)
            except Exception as e:  # noqa: BLE001 — a failure here is a bug report
                failures += 1
                with open(path + ".err", "w") as f:
                    f.write(traceback.format_exc())
                print(f"FAIL {name}: {type(e).__name__}: {e}", flush=True)
    skipped = [f"{a}/{s}: {why}" for (a, s), why in SKIPS.items()]
    print(f"done. failures={failures}; policy-skips={skipped}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
