import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# The two lines above MUST run before any other import (jax locks the
# device count on first init). Everything below is ordinary.
import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from jax import set_mesh  # noqa: E402
from repro.configs.base import SHAPES  # noqa: E402
from repro.configs.registry import (  # noqa: E402
    ARCH_IDS, get_config, shape_applicable,
)
from repro.launch import roofline as RL  # noqa: E402
from repro.launch import tuning  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.specs import build_cell  # noqa: E402

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"


def _mem_dict(ma):
    if ma is None:
        return {}
    return {
        k: getattr(ma, k)
        for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes",
        )
        if hasattr(ma, k)
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool, tuned: bool) -> dict:
    """Lower + compile one cell on the production mesh; return the record."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    base_cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(base_cfg, shape)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": mesh.size, "tuned": tuned,
    }
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec

    cfg, knobs = tuning.resolve(base_cfg, shape, mesh, tuned)
    rec["knobs"] = {k: v for k, v in knobs.items()}

    t0 = time.time()
    cell = build_cell(cfg, shape, mesh, **knobs)
    with set_mesh(mesh):
        jitted = jax.jit(
            cell.fn,
            in_shardings=cell.in_shardings,
            out_shardings=cell.out_shardings,
            donate_argnums=cell.donate_argnums,
        )
        lowered = jitted.lower(*cell.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    roof = RL.derive(ca, hlo, cfg, shape, mesh.size)

    mem = _mem_dict(ma)
    live = (
        mem.get("argument_size_in_bytes", 0)
        + mem.get("temp_size_in_bytes", 0)
        + mem.get("output_size_in_bytes", 0)
        - mem.get("alias_size_in_bytes", 0)
    )
    rec.update(
        status="ok",
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        memory=mem,
        device_live_bytes=live,
        fits_16g=bool(live < 16e9),
        cost={k: ca.get(k) for k in ("flops", "bytes accessed") if k in ca},
        roofline=roof.to_dict(),
        static_info=cell.static_info,
    )
    return rec


def cell_path(arch, shape_name, multi_pod, tuned) -> pathlib.Path:
    tag = "multi" if multi_pod else "single"
    suff = "_tuned" if tuned else ""
    return OUT_DIR / f"{arch}__{shape_name}__{tag}{suff}.json"


def main() -> None:
    ap = argparse.ArgumentParser(description="40-cell multi-pod dry-run")
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--tuned", action="store_true",
                    help="apply §Perf hillclimb overrides from tuning.TUNED")
    ap.add_argument("--all", action="store_true",
                    help="orchestrate every cell as a subprocess")
    ap.add_argument("--both-meshes", action="store_true",
                    help="with --all: run single-pod AND multi-pod")
    ap.add_argument("--force", action="store_true", help="re-run cached cells")
    args = ap.parse_args()
    OUT_DIR.mkdir(parents=True, exist_ok=True)

    if args.all:
        meshes = [False, True] if args.both_meshes else [args.multi_pod]
        todo = []
        for arch in ARCH_IDS:
            for shape_name in SHAPES:
                for mp in meshes:
                    p = cell_path(arch, shape_name, mp, args.tuned)
                    if p.exists() and not args.force:
                        continue
                    todo.append((arch, shape_name, mp))
        print(f"[dryrun] {len(todo)} cells to run", flush=True)
        fails = []
        for i, (arch, shape_name, mp) in enumerate(todo):
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape_name]
            if mp:
                cmd.append("--multi-pod")
            if args.tuned:
                cmd.append("--tuned")
            t0 = time.time()
            r = subprocess.run(cmd, capture_output=True, text=True)
            dt = time.time() - t0
            tag = "multi" if mp else "single"
            if r.returncode != 0:
                fails.append((arch, shape_name, tag))
                print(f"[{i+1}/{len(todo)}] FAIL {arch} {shape_name} {tag} "
                      f"({dt:.0f}s)\n{r.stdout[-2000:]}\n{r.stderr[-2000:]}",
                      flush=True)
            else:
                print(f"[{i+1}/{len(todo)}] ok   {arch} {shape_name} {tag} "
                      f"({dt:.0f}s)", flush=True)
        print(f"[dryrun] done, {len(fails)} failures: {fails}", flush=True)
        sys.exit(1 if fails else 0)

    assert args.arch and args.shape, "--arch and --shape (or --all) required"
    try:
        rec = run_cell(args.arch, args.shape, args.multi_pod, args.tuned)
    except Exception:
        rec = {
            "arch": args.arch, "shape": args.shape,
            "mesh": "2x16x16" if args.multi_pod else "16x16",
            "status": "error", "trace": traceback.format_exc(),
        }
        p = cell_path(args.arch, args.shape, args.multi_pod, args.tuned)
        p.write_text(json.dumps(rec, indent=1))
        print(rec["trace"], file=sys.stderr)
        sys.exit(1)

    p = cell_path(args.arch, args.shape, args.multi_pod, args.tuned)
    p.write_text(json.dumps(rec, indent=1))
    if rec["status"] == "ok":
        r = rec["roofline"]
        print(
            f"{args.arch} {args.shape} {rec['mesh']}: "
            f"compute={r['compute_s']:.4f}s memory={r['memory_s']:.4f}s "
            f"collective={r['collective_s']:.4f}s -> {r['bottleneck']}-bound; "
            f"live={rec['device_live_bytes']/1e9:.2f}GB/dev "
            f"fits16G={rec['fits_16g']} "
            f"roofline_frac={r['roofline_fraction']:.3f}"
        )
    else:
        print(f"{args.arch} {args.shape}: {rec['status']} ({rec.get('reason','')})")


if __name__ == "__main__":
    main()
