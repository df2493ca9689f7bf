#!/usr/bin/env python3
"""Record the small chip traces that ``bench/tests/test_tracing.py``
reduces: a tiny copy of a cell (bench/tests/tiny.py) with the real
Mosaic kernels, its window traced exactly as ``run.py --trace 1`` does.

    python3 bench/tools/record_fixture.py kdda.minibatch [out_dir]  # one chip
    python3 bench/tools/record_fixture.py kddb.full4 [out_dir]      # 2x2 host

Writes, into ``out_dir`` (default ``bench/tests/data``),
``<cell>.xplane.pb.gz``, the compiled step's HLO
(``<cell>.hlo.txt.gz``) and the numbers the reduction gave on the chip
(``<cell>.expected.json``), for the test to compare with.
"""
from __future__ import annotations

import glob
import gzip
import importlib
import json
import pathlib
import shutil
import sys
import tempfile

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (str(BENCH), str(BENCH / "tests"), str(BENCH.parent / "src")):
    sys.path.insert(0, p)

import run  # noqa: E402
import tiny  # noqa: E402
import tracing  # noqa: E402

SECONDS = 0.2


def main(name: str, out: pathlib.Path) -> int:
    import jax
    cell = tiny.tiny_cell(name)
    cell["config"]["admm"]["backend"] = "auto"
    devices = run.chips(cell["chips"])
    driver = importlib.import_module(f"drivers.{cell['traffic']['driver']}")
    r = driver.setup(cell, tiny.SEED)
    tmp = tempfile.mkdtemp(prefix="bench_fixture_")
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation(tracing.WINDOW):
            res = driver.window(r, SECONDS)
        jax.profiler.stop_trace()
        hlo = driver.hlo_text(r)
        out.mkdir(parents=True, exist_ok=True)
        src, = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
        with open(src, "rb") as f, gzip.open(out / f"{name}.xplane.pb.gz",
                                             "wb") as g:
            g.write(f.read())
        with gzip.open(out / f"{name}.hlo.txt.gz", "wt") as f:
            f.write(hlo)
        reduced = tracing.reduce(tracing.load(tmp),
                                 [d.id for d in devices], hlo)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    reduced["units"] = res["units"]
    with open(out / f"{name}.expected.json", "w") as f:
        json.dump(reduced, f, indent=1)
    print(json.dumps(reduced))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1], pathlib.Path(
        sys.argv[2] if len(sys.argv) > 2 else BENCH / "tests" / "data")))
