"""The reduction by the program's own names (spans.py), on small traces
recorded on the chip by ``bench/tools/record_fixture.py`` into
``bench/tests/data/spans`` (a tiny ``kdda.minibatch`` epoch window and a
tiny ``kdda.ps`` window). ``<cell>.spans.json`` pins what ``spans.reduce``
gives them; the identities hold on any trace:

* the device stages and ``unscoped`` add up to ``epoch_other_ms``;
* the PS host terms (self times and time in no program span) add up to
  the traced window, so to the traced round;
* no program span lies outside the window.

    python -m pytest -q bench/tests/test_spans.py
"""
from __future__ import annotations

import gzip
import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import tiny  # noqa: E402, F401  (puts bench/ and src/ on the path)

import spans  # noqa: E402
import tracing  # noqa: E402
from test_tracing import _same  # noqa: E402

DATA = HERE / "data" / "spans"
FIXTURES = ("kdda.minibatch", "kdda.ps")


def _fixture(name):
    from jax._src.profiler import ProfileData
    with gzip.open(DATA / f"{name}.xplane.pb.gz", "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    with gzip.open(DATA / f"{name}.hlo.txt.gz", "rt") as f:
        hlo = f.read()
    with open(DATA / f"{name}.expected.json") as f:
        units = json.load(f)["units"]
    return pd, hlo, units


@pytest.mark.parametrize("name", FIXTURES)
def test_reduction_is_pinned(name):
    pd, hlo, _ = _fixture(name)
    with open(DATA / f"{name}.spans.json") as f:
        expected = json.load(f)
    assert _same(json.loads(json.dumps(spans.reduce(pd, [0], hlo))),
                 expected)


def test_stages_partition_epoch_other():
    pd, hlo, _ = _fixture("kdda.minibatch")
    t = tracing.reduce(pd, [0], hlo)
    op = t["op_s"]
    other = t["busy_s"] - op["grad"] - op["worker_kernel"] \
        - op["server_kernel"]
    stages = spans.reduce(pd, [0], hlo)["stage_s"]
    assert sum(stages.values()) == pytest.approx(other, rel=1e-9)
    named = [k for k, v in stages.items() if v > 0 and k != spans.UNSCOPED]
    assert {"asybadmm.gather", "asybadmm.minibatch", "asybadmm.select",
            "asybadmm.worker_update", "asybadmm.server_update"} <= set(named)


def test_ps_host_terms_add_up_to_the_round():
    pd, hlo, units = _fixture("kdda.ps")
    r = spans.reduce(pd, [0], hlo)
    host = sum(v["self_s"] for v in r["host"].values()) + r["host_other_s"]
    assert host / units == pytest.approx(r["window_s"] / units, rel=0.02)
    # the window replays the set-up's rounds, whose selections the
    # engine keeps, so a short window need not read one
    assert spans.HOST_SPANS - {spans.COMPILE, "ps.sync.select"} \
        <= set(r["host"]) <= spans.HOST_SPANS
    assert r["compiles"] == 0


@pytest.mark.parametrize("name", FIXTURES)
def test_no_program_span_outside_window(name):
    pd, _, _ = _fixture(name)
    host = pd.find_plane_with_name("/host:CPU")
    events = [ev for line in host.lines for ev in line.events]
    (w,) = [ev for ev in events if ev.name == tracing.WINDOW]
    program = [ev for ev in events if ev.name in spans.HOST_SPANS]
    assert program or name == "kdda.minibatch"
    for ev in program:
        assert w.start_ns <= ev.start_ns
        assert ev.start_ns + ev.duration_ns <= w.start_ns + w.duration_ns


def test_ps_gaps_are_labeled_by_program_spans():
    pd, hlo, _ = _fixture("kdda.ps")
    gaps = spans.idle_gaps(pd, [0])
    assert any(label.startswith("ps.") for label, _ in gaps)
    # the same gaps as tracing.py's, which knows only the benchmark's
    outside = tracing.reduce(pd, [0], hlo)["breakdown"]["idle_gaps"]
    assert [g for _, g in gaps] == [g for _, g in outside]
    assert all(label.startswith("bench_") for label, _ in outside)


# reader -> (fixture it reads, whether it reads something there)
READERS = {
    "gather_ms": ("kdda.minibatch", True),
    "minibatch_draw_ms": ("kdda.minibatch", True),
    "pack_ms": ("kdda.minibatch", True),
    "select_ms": ("kdda.minibatch", True),
    "update_glue_ms": ("kdda.minibatch", True),
    "ring_push_ms": ("kdda.minibatch", True),
    "grad_glue_ms": ("kdda.minibatch", True),
    "unscoped_ms": ("kdda.minibatch", True),
    "exchange_ms": ("kdda.minibatch", False),    # no mesh, no exchange
    "window_compiles.epoch": ("kdda.minibatch", True),
    "ps_worker_host_ms": ("kdda.ps", True),
    "ps_commit_ms": ("kdda.ps", True),
    "ps_sync_wait_ms": ("kdda.ps", True),
    "ps_syncs": ("kdda.ps", True),
    "ps_host_other_ms": ("kdda.ps", True),
    "window_compiles.ps": ("kdda.ps", True),
}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader(metric):
    """Each reader of ``spans.reduce``'s output reads its cell's trace,
    and reads nothing without it (as from ``bench/run.py`` today)."""
    import run
    read = run.metric_reader(metric)
    name, reads = READERS[metric]
    pd, hlo, units = _fixture(name)
    value = read({"program": spans.reduce(pd, [0], hlo), "units": units})
    assert (value is not None) == reads
    if reads:
        assert value >= 0
    assert read({"units": units}) is None


@pytest.mark.parametrize("op_name,stage", [
    ("jit(asybadmm_step)/asybadmm.gather/gather", "asybadmm.gather"),
    ("jit(asybadmm_step)/asybadmm.grad/asybadmm.minibatch/sort",
     "asybadmm.minibatch"),
    ("jit(s)/asybadmm.grad/vmap(asybadmm.pack)/slice", "asybadmm.pack"),
    ("jit(s)/shard_map/asybadmm.server_update/asybadmm.exchange/psum",
     "asybadmm.exchange"),
    ("jit(s)/jit(_threefry_split)/slice", spans.UNSCOPED),
    ("", spans.UNSCOPED),
])
def test_stage_is_the_innermost_scope(op_name, stage):
    assert spans.stage_of(op_name) == stage
