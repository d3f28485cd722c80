"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh).

A CPU-only tool.  The first lines (before any jax-touching import) pin
this process, and the children it starts, to the CPU platform with 512
placeholder host devices, so the production meshes can be built and it
can never claim an accelerator.
"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

import argparse      # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import subprocess    # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402

import jax           # noqa: E402

from repro.configs.registry import ASSIGNED, get_config   # noqa: E402
from repro.launch import hlo_analysis as HA               # noqa: E402
from repro.launch import mesh as M                        # noqa: E402
from repro.launch.specs import INPUT_SHAPES, make_target  # noqa: E402

OUT_DIR = os.path.join(os.path.dirname(__file__), "../../../experiments/dryrun")

# ---------------------------------------------------------------------------
# One combo
# ---------------------------------------------------------------------------


def run_one(arch: str, shape: str, multi_pod: bool) -> dict:
    from repro.sharding import partition as SH
    cfg = get_config(arch)
    mesh = M.make_production_mesh(multi_pod=multi_pod)
    SH.set_current_mesh(mesh)          # enables in-model constraints
    chips = mesh.size
    target = make_target(cfg, shape, mesh)
    rec = {"arch": arch, "shape": shape,
           "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
           **target.static_meta}

    t0 = time.perf_counter()
    with mesh:
        jitted = jax.jit(target.fn, donate_argnums=target.donate_argnums)
        lowered = jitted.lower(*target.args)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()

    rec["lower_s"] = round(t1 - t0, 2)
    rec["compile_s"] = round(t2 - t1, 2)

    # -- memory ------------------------------------------------------------
    try:
        ma = compiled.memory_analysis()
        rec["memory"] = {
            k: int(getattr(ma, k)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(ma, k)}
        if hasattr(ma, "peak_memory_in_bytes"):
            rec["memory"]["peak_memory_in_bytes"] = int(ma.peak_memory_in_bytes)
    except Exception as e:  # CPU backend may not support it
        rec["memory"] = {"error": str(e)}

    # -- cost ----------------------------------------------------------------
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        rec["cost"] = {k: float(v) for k, v in ca.items()
                       if isinstance(v, (int, float)) and
                       (k in ("flops", "bytes accessed", "transcendentals")
                        or k.startswith("bytes accessed"))}
    except Exception as e:
        rec["cost"] = {"error": str(e)}

    # -- trip-count-aware HLO analysis (flops/bytes/collectives) ----------
    try:
        hlo = compiled.as_text()
        ha = HA.analyze(hlo)
        rec["hlo"] = {"flops": ha["flops"], "bytes": ha["bytes"],
                      "n_dots": ha["n_dots"],
                      "bytes_by_op": ha["bytes_by_op"]}
        rec["collectives"] = ha["collectives"]
        rec["hlo_lines"] = hlo.count("\n")
    except Exception as e:
        rec["collectives"] = {"error": str(e)}
        rec["hlo"] = {"error": str(e)}

    # -- model flops (roofline 'useful compute') ----------------------------
    pc = cfg.param_counts()
    info = INPUT_SHAPES[shape]
    tokens = info["batch"] * (info["seq"] if shape != "decode_32k" else 1)
    if info["kind"] == "decode":
        tokens = info["batch"]  # one token per slot
    nonembed_total = pc["total"] - pc["embed"]
    nonembed_active = pc["active"] - pc["embed"]
    mult = 6 if info["kind"] == "train" else 2
    rec["model_flops"] = {
        "params_total": pc["total"], "params_active": pc["active"],
        "tokens": tokens,
        "flops": mult * nonembed_active * tokens,
    }
    return rec


def applicable(arch: str, shape: str) -> bool:
    return True  # every combo lowers (long-context override covers 500k)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every combo in subprocesses")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=OUT_DIR)
    ap.add_argument("--suffix", default=None,
                    help="artifact tag suffix for §Perf variants")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)

    if args.all:
        failures = []
        combos = [(a, s, mp)
                  for a in ASSIGNED
                  for s in INPUT_SHAPES
                  for mp in ((False, True) if args.both_meshes else (False,))]
        for i, (a, s, mp) in enumerate(combos):
            tag = f"{a}__{s}__{'2x16x16' if mp else '16x16'}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path):
                print(f"[{i+1}/{len(combos)}] {tag}: cached", flush=True)
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", a, "--shape", s, "--out", args.out]
            if mp:
                cmd.append("--multi-pod")
            t0 = time.time()
            r = subprocess.run(cmd, capture_output=True, text=True)
            ok = r.returncode == 0
            print(f"[{i+1}/{len(combos)}] {tag}: "
                  f"{'ok' if ok else 'FAIL'} ({time.time()-t0:.0f}s)",
                  flush=True)
            if not ok:
                failures.append(tag)
                with open(os.path.join(args.out, tag + ".err"), "w") as f:
                    f.write(r.stdout[-4000:] + "\n" + r.stderr[-8000:])
        print(f"done; {len(failures)} failures: {failures}")
        return 1 if failures else 0

    assert args.arch and args.shape, "--arch/--shape required (or --all)"
    rec = run_one(args.arch, args.shape, args.multi_pod)
    from repro import perf_flags
    rec["perf_opts"] = perf_flags.active()
    tag = (f"{args.arch}__{args.shape}__"
           f"{'2x16x16' if args.multi_pod else '16x16'}")
    if args.suffix:
        tag += f"__{args.suffix}"
    with open(os.path.join(args.out, tag + ".json"), "w") as f:
        json.dump(rec, f, indent=2)
    print(json.dumps({k: rec[k] for k in
                      ("arch", "shape", "mesh", "lower_s", "compile_s")},
                     indent=None))
    print("memory:", rec["memory"])
    print("hlo:", rec.get("hlo"))
    print("collectives:", rec["collectives"].get("total_bytes"),
          rec["collectives"].get("counts"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
