"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is looked up in BENCHMARK.json; its configuration, traffic mix
and per-layer readers are found by name under bench/. The program under
test is imported from this checkout's ``src/``. Without a TPU, or with
fewer chips than the cell asks for, the run exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def _import_program() -> None:
    """Put this checkout (and its ``src/``) first on the path and make
    sure `repro` comes from there, not from an installed copy."""
    for p in (str(ROOT / "src"), str(ROOT)):
        if p in sys.path:
            sys.path.remove(p)
        sys.path.insert(0, p)
    here = (ROOT / "src" / "repro").resolve()
    if not here.is_dir():
        raise SystemExit(f"no program under {ROOT / 'src'}")
    import repro
    where = [Path(p).resolve() for p in repro.__path__]
    if where != [here]:
        raise SystemExit(f"repro imported from {where}, not from {here}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    _import_program()
    from bench import core

    try:
        spec = core.benchmark()
        cell = core.cell(args.workload, spec)
        mix = core.traffic(cell["traffic"])
        driver = core.kind(mix["kind"])
        devs = core.require_chips(cell["chips"])
        core.enable_compile_cache()
        run = driver.Run(spec=spec, cell=cell, mix=mix, devs=devs,
                         seed=args.seed, seconds=args.seconds,
                         trace=bool(args.trace), t_start=T_START)
        result, checks = run.execute()
    except core.HarnessError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    except Exception:
        traceback.print_exc()
        return 1
    core.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.stdout.reconfigure(line_buffering=True)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    raise SystemExit(main())
