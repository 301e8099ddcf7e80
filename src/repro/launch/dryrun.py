import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede any jax-importing import: jax locks the device count on
# first init, and the production meshes below need 512 host placeholders.
# This is a CPU tool: pinning the platform (inherited by the --all
# children) keeps it off any accelerator the host has.

import argparse      # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import subprocess    # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

"""Multi-pod dry-run: prove every (arch × shape × mesh) cell lowers,
partitions, and compiles coherently — sharding mismatches, unsupported
collectives, and absurd per-device memory all surface here, without
hardware.

For each cell:
    lowered  = jax.jit(step_fn).lower(*sharded ShapeDtypeStructs)
    compiled = lowered.compile()
    print(compiled.memory_analysis())   # proves it fits
    print(compiled.cost_analysis())     # FLOPs/bytes → §Roofline
plus a pass over the partitioned HLO summing collective wire bytes
(ring-model per-chip estimates, classified by op kind) → §Roofline's
collective term.

Usage:
    python -m repro.launch.dryrun --arch olmo-1b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] --out results.jsonl
(--all fans each cell into a subprocess: isolation against OOM/compile
state, fresh device count, one JSON record per line.)
"""

_COLL_RE = re.compile(
    r"=\s*(?:\([^)]*\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
_SHAPE_RE = re.compile(r"=\s*\(?((?:[a-z0-9]+\[[0-9,]*\](?:\{[0-9,]*\})?,?\s*)+)\)?\s*(?:all|collective)")
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}


def _shape_bytes(tok: str) -> int:
    m = re.match(r"([a-z0-9]+)\[([0-9,]*)\]", tok)
    if not m:
        return 0
    dt, dims = m.groups()
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dt, 4)


def _group_size(line: str, total_devices: int) -> int:
    m = re.search(r"replica_groups=\{\{([0-9,]+)\}", line)
    if m:
        return len(m.group(1).split(","))
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)  # iota form [n,g]
    if m:
        return int(m.group(2))
    return total_devices


def collective_wire_bytes(hlo_text: str, total_devices: int) -> dict:
    """Per-chip wire-byte estimates by collective kind (ring model):
    AR 2·X·(n−1)/n, AG X_out·(n−1)/n, RS X_out·(n−1), A2A X·(n−1)/n,
    permute X.  Shapes in the partitioned module are already per-device."""
    out = {k: 0.0 for k in
           ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
            "collective-permute")}
    counts = dict.fromkeys(out, 0)
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group(1)
        sm = re.search(r"=\s*\(?([^)=]*?)\s*" + re.escape(kind), line)
        toks = re.findall(r"[a-z0-9]+\[[0-9,]*\]", sm.group(1)) if sm else []
        x = sum(_shape_bytes(t) for t in toks)
        n = _group_size(line, total_devices)
        if n <= 1:
            continue
        if kind == "all-reduce":
            w = 2 * x * (n - 1) / n
        elif kind == "all-gather":
            w = x * (n - 1) / n
        elif kind == "reduce-scatter":
            w = x * (n - 1)
        elif kind == "all-to-all":
            w = x * (n - 1) / n
        else:
            w = x
        out[kind] += w
        counts[kind] += 1
    out["counts"] = counts
    out["total"] = sum(v for k, v in out.items() if isinstance(v, float))
    return out


def run_cell(arch: str, shape: str, *, multi_pod: bool, policy: str = "fier",
             budget: int = 4096, dist_mode: str = "local", verbose: bool = True,
             cost_depth: int | None = None, cost_depth_enc: int | None = None,
             flops_only: bool = False, strategy: str = "tp") -> dict:
    import jax
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import build_cell
    from repro.models import tuning

    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = {
        "arch": arch, "shape": shape, "policy": policy,
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "devices": mesh.devices.size, "multi_pod": multi_pod,
        "dist_mode": dist_mode, "budget": budget,
        "cost_depth": cost_depth, "cost_depth_enc": cost_depth_enc,
        "strategy": strategy,
    }
    t0 = time.time()
    cell = build_cell(arch, shape, mesh, policy_kind=policy, budget=budget,
                      dist_mode=dist_mode, cost_depth=cost_depth,
                      cost_depth_enc=cost_depth_enc, strategy=strategy)
    rec["kind"] = cell.kind

    if flops_only:
        # scan-aware jaxpr FLOP count (global) — no compile
        import sys as _sys
        _sys.path.insert(0, "benchmarks")
        from flopcount import count_fn_flops

        with jax.set_mesh(mesh):
            rec["jaxpr_flops_global"] = float(count_fn_flops(cell.fn, *cell.args))
        rec["jaxpr_flops_per_device"] = rec["jaxpr_flops_global"] / mesh.devices.size
        _finish_model_flops(rec, arch, shape, cell, mesh)
        if verbose:
            print(f"[flops] {arch} × {shape}: global={rec['jaxpr_flops_global']:.3e} "
                  f"per-device={rec['jaxpr_flops_per_device']:.3e}")
        return rec

    # NOTE on donation: deployed steps donate the cache/state so outputs
    # alias inputs; we lower WITHOUT donation here because XLA:CPU's
    # buffer accounting degrades under donation (f32 shadow copies of
    # bf16 slabs — see EXPERIMENTS.md §Dry-run caveats).  Deployment
    # memory ≈ args + temp (out aliased).
    with jax.set_mesh(mesh), tuning.tuned(**cell.tuning):
        lowered = jax.jit(cell.fn).lower(*cell.args)
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)

    mem = compiled.memory_analysis()
    for field in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
        rec[field] = int(getattr(mem, field, -1))
    rec["per_device_bytes"] = (
        rec["argument_size_in_bytes"] + rec["temp_size_in_bytes"]
    )
    cost = compiled.cost_analysis()
    rec["flops"] = float(cost.get("flops", -1.0))
    rec["bytes_accessed"] = float(cost.get("bytes accessed", -1.0))
    text = compiled.as_text()
    rec["collectives"] = collective_wire_bytes(text, mesh.devices.size)
    _finish_model_flops(rec, arch, shape, cell, mesh)
    if verbose:
        print(f"[dryrun] {arch} × {shape} ({cell.kind}) on {rec['mesh']}:")
        print(f"  memory_analysis: args={rec['argument_size_in_bytes']/1e9:.2f}GB "
              f"temp={rec['temp_size_in_bytes']/1e9:.2f}GB "
              f"out={rec['output_size_in_bytes']/1e9:.2f}GB (per device)")
        print(f"  cost_analysis: flops={rec['flops']:.3e} "
              f"bytes={rec['bytes_accessed']:.3e} (per device)")
        print(f"  collectives (wire bytes/chip): " +
              ", ".join(f"{k}={v:.2e}" for k, v in rec["collectives"].items()
                        if isinstance(v, float) and v > 0))
    return rec


def _finish_model_flops(rec, arch, shape, cell, mesh):
    """6·N_active·tokens (train; the 6 covers fwd+bwd) or 2·N·tokens
    (prefill/decode fwd-only)."""
    from repro.configs import SHAPES, get_config

    cfg = get_config(arch)
    sh = SHAPES[shape]
    n_active = cfg.active_param_count()
    tokens = sh.global_batch * (sh.seq_len if cell.kind != "decode" else 1)
    mult = 6 if cell.kind == "train" else 2
    rec["model_flops_global"] = float(mult * n_active * tokens)
    rec["model_flops_per_device"] = rec["model_flops_global"] / mesh.devices.size


def all_cells(multi_pod_too: bool = True):
    from repro.configs import ARCHS, shape_cells

    for arch in ARCHS:
        for shape in shape_cells(arch):
            yield arch, shape, False
            if multi_pod_too:
                yield arch, shape, True


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--policy", default="fier",
                    choices=["fier", "quest", "full"])
    ap.add_argument("--budget", type=int, default=4096)
    ap.add_argument("--dist-mode", default="local", choices=["local", "exact"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--json", action="store_true", help="print record as JSON line")
    ap.add_argument("--timeout", type=int, default=3600)
    ap.add_argument("--cost-depth", type=int, default=None,
                    help="roofline extrapolation: rebuild at this depth, unrolled")
    ap.add_argument("--cost-depth-enc", type=int, default=None)
    ap.add_argument("--flops-only", action="store_true",
                    help="jaxpr FLOP count only (no compile)")
    ap.add_argument("--strategy", default="tp", choices=["tp", "fsdp_pure"])
    args = ap.parse_args()

    if args.all:
        failures = []
        sink = open(args.out, "a") if args.out else None
        for arch, shape, mp in all_cells(multi_pod_too=not args.single_pod_only):
            cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
                   "--shape", shape, "--policy", args.policy, "--json",
                   "--budget", str(args.budget), "--dist-mode", args.dist_mode]
            if mp:
                cmd.append("--multi-pod")
            t0 = time.time()
            r = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=args.timeout,
                               env={**os.environ, "PYTHONPATH": "src"})
            dt = time.time() - t0
            tag = f"{arch} × {shape} × {'2pod' if mp else '1pod'}"
            if r.returncode == 0:
                line = r.stdout.strip().splitlines()[-1]
                print(f"PASS {tag} ({dt:.0f}s)")
                if sink:
                    sink.write(line + "\n")
                    sink.flush()
            else:
                print(f"FAIL {tag}:\n{r.stderr[-2000:]}")
                failures.append(tag)
        if sink:
            sink.close()
        print(f"\n{'ALL PASS' if not failures else f'{len(failures)} FAILURES'}")
        for f in failures:
            print(" -", f)
        return 1 if failures else 0

    rec = run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                   policy=args.policy, budget=args.budget,
                   dist_mode=args.dist_mode, verbose=not args.json,
                   cost_depth=args.cost_depth, cost_depth_enc=args.cost_depth_enc,
                   flops_only=args.flops_only, strategy=args.strategy)
    if args.json:
        print(json.dumps(rec))
    if args.out:
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
