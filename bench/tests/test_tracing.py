"""The trace reduction (tracing.py), on small traces recorded on the chip
by ``bench/tools/record_fixture.py`` and kept in ``bench/tests/data``
with the numbers the reduction gives them (``*.expected.json``): every
later PR computes busy time, kernel time, the named-scope sum and the
collective split the same way. Plus the interval arithmetic on
made-up events.

    python -m pytest -q bench/tests/test_tracing.py
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

import tracing  # noqa: E402

DATA = HERE / "data"
FIXTURES = {"kdda.minibatch": 1, "kddb.full4": 4}


def _fixture(name):
    from jax._src.profiler import ProfileData
    with gzip.open(DATA / f"{name}.xplane.pb.gz", "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    with gzip.open(DATA / f"{name}.hlo.txt.gz", "rt") as f:
        hlo = f.read()
    with open(DATA / f"{name}.expected.json") as f:
        expected = json.load(f)
    return pd, hlo, expected


@pytest.mark.parametrize("name", list(FIXTURES))
def test_fixture_reduction_is_pinned(name):
    pd, hlo, expected = _fixture(name)
    got = tracing.reduce(pd, list(range(FIXTURES[name])), hlo)
    got["units"] = expected["units"]
    assert _same(json.loads(json.dumps(got)), expected)


def _same(a, b) -> bool:
    if isinstance(a, dict):
        return a.keys() == b.keys() and all(_same(a[k], b[k]) for k in a)
    if isinstance(a, (list, tuple)):
        return len(a) == len(b) and all(_same(x, y) for x, y in zip(a, b))
    if isinstance(a, float):
        return a == pytest.approx(b, rel=1e-9, abs=1e-12)
    return a == b


@pytest.mark.parametrize("name", list(FIXTURES))
def test_fixture_layers_add_up(name):
    pd, hlo, _ = _fixture(name)
    r = tracing.reduce(pd, list(range(FIXTURES[name])), hlo)
    op = r["op_s"]
    assert 0 < r["busy_s"] <= r["window_s"]
    for cat in ("grad", "worker_kernel", "server_kernel"):
        assert op[cat] > 0, cat
    assert sum(op[c] for c in tracing.CATEGORIES) <= r["busy_s"] * (1 + 1e-9)
    assert 0 <= op["collective_exposed"] <= op["collective"] * (1 + 1e-9)
    if FIXTURES[name] > 1:
        assert op["collective"] > 0
    else:
        assert op["collective"] == 0
    assert len(r["breakdown"]["device_ops"]) <= 10
    assert len(r["breakdown"]["idle_gaps"]) <= 10
    # without the program's HLO no instruction has a scope: no grad
    assert tracing.reduce(pd, list(range(FIXTURES[name])))["op_s"][
        "grad"] == 0


def test_scatter_without_op_name_is_gradient():
    """The SPMD program's gradient scatter carries no op_name; it and
    the fusion that holds it still count as the loss's."""
    _, hlo, _ = _fixture("kddb.full4")
    scopes = tracing.hlo_scopes(hlo)
    assert tracing.GRAD_SCOPE in scopes["fusion.2"]
    assert "scatter(" in next(line for line in hlo.splitlines()
                              if "%scatter.4 =" in line)


def test_leaves_drop_enclosing_events():
    ev = [("while.1", 0.0, 10.0), ("a", 1.0, 3.0), ("b", 4.0, 9.0),
          ("c", 12.0, 13.0)]
    assert [e[0] for e in tracing.leaves(ev)] == ["a", "b", "c"]


def test_union_and_minus():
    a = [(0.0, 4.0), (2.0, 6.0), (10.0, 12.0)]
    b = [(1.0, 2.0), (5.0, 11.0)]
    assert tracing.union(a) == [(0.0, 6.0), (10.0, 12.0)]
    # union(a) minus union(b): [0,1) + [2,5) + [11,12) = 1 + 3 + 1
    assert tracing.minus(a, b) == 5.0
    assert tracing.minus(a, []) == 8.0
    assert tracing.minus([], b) == 0.0


def test_categories():
    scopes = {"fusion.3": "jit(f)/vmap(transpose(jvp(bench_sparse_logreg)))"
                          "/scatter-add", "fusion.4": "jit(f)/add"}
    assert tracing.category("%fusion.3 = f32[8] fusion(...)", scopes) \
        == "grad"
    assert tracing.category("%fusion.4 = f32[8] fusion(...)", scopes) is None
    assert tracing.category(
        '%admm_worker_select_update.1 = (f32[2]) custom-call(...), '
        'custom_call_target="tpu_custom_call"', scopes) == "worker_kernel"
    assert tracing.category(
        '%prox_consensus.2 = f32[2] custom-call(...), '
        'custom_call_target="tpu_custom_call"', scopes) == "server_kernel"
    assert tracing.category(
        "%all-to-all.1 = f32[2] all-to-all(f32[2] %x)", scopes) \
        == "collective"
    assert tracing.category(
        "%all-reduce-start.2 = f32[2] all-reduce-start(f32[2] %x)",
        scopes) == "collective"
