"""Multi-pod dry-run (deliverable e).

Lowers + compiles every (architecture × input shape) cell on the 16×16
single-pod mesh AND the 2×16×16 multi-pod mesh, records
``memory_analysis()`` / ``cost_analysis()`` / per-collective byte counts
into ``results/dryrun_manifest.json`` (incremental + atomic), and fails
loudly on sharding bugs.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun [--arch A] [--shape S]
        [--mesh single|multi|both] [--skip-existing] [--list]
"""
from __future__ import annotations

import os  # XLA_FLAGS must precede every other jax-touching import
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse  # noqa: E402
import json
import re
import time
import traceback
from pathlib import Path

import jax

MANIFEST = Path(__file__).resolve().parents[3] / "results" / "dryrun_manifest.json"

_COLL_RE = re.compile(
    r"=\s*(?:\([^)]*\)|(?P<dtype>[a-z0-9]+)\[(?P<dims>[0-9,]*)\])"
    r"[^=]*?\b(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)\b"
)
_TUPLE_ELEM_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum per-partition result bytes of every collective op in optimized HLO."""
    out: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        lhs = line.split("=", 1)[0] + "=" + line.split("=", 1)[1]
        # result type: between '=' and the op name
        head = line.split(m.group("op"))[0]
        total = 0
        if m.group("dtype"):
            total = _shape_bytes(m.group("dtype"), m.group("dims"))
        else:  # tuple result
            seg = head.split("=", 1)[-1]
            for dt, dims in _TUPLE_ELEM_RE.findall(seg):
                total += _shape_bytes(dt, dims)
        out[op] = out.get(op, 0) + total
    return out


def _load_manifest() -> dict:
    if MANIFEST.exists():
        return json.loads(MANIFEST.read_text())
    return {}


def _save_manifest(m: dict) -> None:
    MANIFEST.parent.mkdir(parents=True, exist_ok=True)
    tmp = MANIFEST.with_suffix(".tmp")
    tmp.write_text(json.dumps(m, indent=1, sort_keys=True))
    tmp.replace(MANIFEST)


def run_cell(arch_id: str, shape: str, mesh_kind: str) -> dict:
    from repro.launch.cells import build_cell
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    with jax.set_mesh(mesh):
        cell = build_cell(arch_id, shape, mesh)
        lowered = cell.fn.lower(*cell.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        rec: dict = {
            "status": "ok",
            "kind": cell.kind,
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "meta": cell.meta,
        }
        try:
            ma = compiled.memory_analysis()
            rec["memory"] = {
                k: int(getattr(ma, k))
                for k in (
                    "argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes", "generated_code_size_in_bytes",
                    "alias_size_in_bytes",
                )
                if hasattr(ma, k)
            }
        except Exception as e:  # CPU backend may not support it
            rec["memory"] = {"error": str(e)}
        try:
            ca = compiled.cost_analysis()
            rec["cost"] = {
                k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float)) and (
                    k in ("flops", "bytes accessed", "optimal_seconds")
                    or k.startswith("bytes accessed")
                )
            }
        except Exception as e:
            rec["cost"] = {"error": str(e)}
        try:
            rec["collectives"] = collective_bytes(compiled.as_text())
        except Exception as e:
            rec["collectives"] = {"error": str(e)}
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()

    from repro.launch.cells import all_cells

    cells = all_cells()
    meshes = {"single": ["single"], "multi": ["multi"],
              "both": ["single", "multi"]}[args.mesh]
    manifest = _load_manifest()
    n_ok = n_skip = n_fail = 0

    for arch_id, shape, skip in cells:
        if args.arch and arch_id != args.arch:
            continue
        if args.shape and shape != args.shape:
            continue
        for mk in meshes:
            key = f"{arch_id}|{shape}|{mk}"
            if skip:
                manifest[key] = {"status": "skipped", "reason": skip}
                n_skip += 1
                print(f"SKIP {key}: {skip}")
                continue
            if args.list:
                print(f"CELL {key}")
                continue
            if args.skip_existing and manifest.get(key, {}).get("status") == "ok":
                print(f"HAVE {key}")
                continue
            print(f"RUN  {key} ...", flush=True)
            try:
                rec = run_cell(arch_id, shape, mk)
                manifest[key] = rec
                n_ok += 1
                flops = rec.get("cost", {}).get("flops", float("nan"))
                print(
                    f"  ok: lower {rec['lower_s']}s compile {rec['compile_s']}s"
                    f" flops/dev {flops:.3e}"
                    f" coll {rec.get('collectives', {})}"
                )
            except Exception as e:
                manifest[key] = {
                    "status": "fail",
                    "error": f"{type(e).__name__}: {e}",
                    "trace": traceback.format_exc()[-2000:],
                }
                n_fail += 1
                print(f"  FAIL: {type(e).__name__}: {e}")
            _save_manifest(manifest)

    print(f"\ndone: ok={n_ok} skip={n_skip} fail={n_fail}")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
