"""The serving path's trace marks: host spans in ``HybridEngine.search`` on
the profiler's clock, and named scopes on the beam round's device steps.
Both are marks only: answers with and without a trace are the same bits."""

import glob
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.graphs.adjacency import Graph
from repro.kernels import ops as kops
from repro.pq import base, pack, train_pq, train_pq_fs4
from repro.search import beam
from repro.search.engine import HybridEngine

N, D, R, Q = 400, 16, 8, 6
LAYOUTS = ["u8", "fs4"]
SPANS = ("rpq.search.lut", "rpq.search.beam", "rpq.search.rerank")
SCOPES = ("beam.select", "beam.visited", "beam.merge")


@pytest.fixture(scope="module")
def corpus():
    r = np.random.default_rng(3)
    x = jnp.asarray(r.normal(size=(N, D)).astype(np.float32))
    q = jnp.asarray(r.normal(size=(Q, D)).astype(np.float32))
    # any graph routes; a random one keeps the set-up cheap
    nbrs = jnp.asarray(r.integers(0, N, size=(N, R)).astype(np.int32))
    return x, q, Graph(nbrs, jnp.int32(0))


@pytest.fixture(scope="module", params=LAYOUTS)
def engine(request, corpus):
    x, _, graph = corpus
    key = jax.random.PRNGKey(0)
    if request.param == "fs4":
        model = train_pq_fs4(key, x, 8, iters=4)
        codes = pack.pack_codes(base.encode(model, x))
        lut_fn = lambda qq: base.build_lut(model, qq, quantize=True)  # noqa: E731
    else:
        model = train_pq(key, x, 4, 32, iters=4)
        codes = base.encode(model, x)
        lut_fn = lambda qq: base.build_lut(model, qq)  # noqa: E731
    return HybridEngine(graph, codes, lut_fn, vectors=x)


def _traced(tmp_path, fn):
    """Run ``fn`` inside a ``caller`` span under the profiler; return its
    result and the trace's host spans as {name: [(start_ns, end_ns)]}."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("caller"):
            out = jax.block_until_ready(fn())
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    spans = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "caller" or ev.name.startswith("rpq."):
                    spans.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    return out, spans


def test_search_spans_nest_in_caller(engine, corpus, tmp_path):
    _, q, _ = corpus
    engine.search(q, k=5, h=16)                   # compile outside the trace
    _, spans = _traced(tmp_path, lambda: engine.search(q, k=5, h=16))
    assert set(spans) == {"caller", "rpq.search", *SPANS}
    assert all(len(v) == 1 for v in spans.values())
    (c0, c1), (s0, s1) = spans["caller"][0], spans["rpq.search"][0]
    assert c0 <= s0 < s1 <= c1
    # lut, beam, rerank: in that order, inside rpq.search, one clock
    edges = [t for name in SPANS for t in spans[name][0]]
    assert edges == sorted(edges)
    assert s0 <= edges[0] and edges[-1] <= s1


def test_answers_same_with_and_without_trace(engine, corpus, tmp_path):
    _, q, _ = corpus
    plain = jax.block_until_ready(engine.search(q, k=5, h=16))
    traced, _ = _traced(tmp_path, lambda: engine.search(q, k=5, h=16))
    for field in ("ids", "dists", "hops", "n_dist", "rounds", "truncated"):
        np.testing.assert_array_equal(np.asarray(getattr(plain, field)),
                                      np.asarray(getattr(traced, field)))


@pytest.mark.parametrize("expand", [1, 2])
def test_beam_round_scopes_in_compiled_hlo(engine, corpus, expand):
    """The compiled beam's ops carry the round's scopes in their op_name
    metadata (the lowered text without debug info leaves them out)."""
    _, q, graph = corpus
    luts = engine.lut_fn(q)
    dist_fn = beam.make_adc_dist_fn(kops.pad_sentinel_row(engine.codes),
                                    packed=isinstance(luts, pack.QuantizedLUT))
    hlo = beam.beam_search.lower(graph.neighbors, graph.medoid, luts, dist_fn,
                                 h=16, expand=expand).compile().as_text()
    for scope in SCOPES:
        assert f"/{scope}/" in hlo, scope
