"""Scoped spans of the live continuous-decode path.

A tiny paged engine served through ``Server`` and
``ContinuousEngineAdapter`` with an enabled tracer: the server, the
decode session's scheduling and its jitted calls each leave their
scopes (``server.*``, ``sched.*``, ``step.*``) on the tracer's clock,
one ``step.window`` per host sync and one ``step.prefill`` per prefill
call, without adding a compile.  The default tracer records nothing.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import transformer as tfm
from repro.serving.adapters import ContinuousEngineAdapter
from repro.serving.api import InferRequest, Server, ServerConfig
from repro.serving.continuous import ContinuousBatchingEngine
from repro.telemetry.trace import NULL_TRACER, Tracer, validate_trace


@pytest.fixture(scope="module")
def lm():
    cfg = get_smoke_config("stablelm-3b").replace(remat=False,
                                                  kv_block_size=8)
    return cfg, tfm.init_lm(cfg, jax.random.PRNGKey(0))


def _serve(lm, tracer, n=9):
    """Push ``n`` requests (poking between arrivals), then finish;
    returns the engine, the decode session and every response."""
    cfg, params = lm
    engine = ContinuousBatchingEngine(cfg, params, n_slots=3, max_seq=64,
                                      sync_every=4)
    adapter = ContinuousEngineAdapter(engine, prompt_len=8)
    server = Server(adapter, ServerConfig(path="continuous-decode"),
                    tracer=tracer)
    rng = np.random.default_rng(1)
    server.start()
    for i in range(n):
        server.push(InferRequest(
            rid=i, arrival_s=0.01 * i, kind="generate", max_new=3 + i % 5,
            payload=rng.integers(0, cfg.vocab, 8).astype(np.int32)))
        if i % 3 == 2:
            server.poke(0.01 * i)
    return engine, adapter.session, server.finish()


def test_serving_loop_scopes_each_layer(lm):
    tracer = Tracer()
    engine, sess, out = _serve(lm, tracer)
    assert sorted(r.rid for r in out) == list(range(9))
    assert sess.tracer is tracer
    by_id = {s.span_id: s for s in tracer.spans}

    def parent(s):
        return by_id[s.parent_id].name if s.parent_id else None

    assert len(tracer.find("step.window")) == sess.host_syncs > 1
    assert len(tracer.find("step.prefill")) == sess.prefill_calls > 1
    assert len(tracer.find("sched.harvest")) == sess.host_syncs
    assert len(tracer.find("sched.seat")) == sess.prefill_calls
    assert tracer.find("sched.refill") and tracer.find("sched.table")
    # the scheduler's and the model step's scopes sit directly under
    # the session's advance, which sits under the server's calls (or
    # at the root for the drain in finish)
    for s in tracer.spans:
        if s.name.startswith(("sched.", "step.")) \
                and s.name != "sched.advance":
            assert parent(s) == "sched.advance", s
    assert {parent(s) for s in tracer.find("sched.advance")} == {
        "server.push", "server.poke", None}
    assert len(tracer.find("server.push")) == 9
    assert len(tracer.find("server.poke")) == 3
    assert {parent(s) for s in tracer.find("server.admit")} == {
        "server.push"}
    assert {parent(s) for s in tracer.find("server.absorb")} <= {
        "server.push", "server.poke", None}
    # on the tracer's clock: each scope lies inside its parent
    for s in tracer.spans:
        if s.name.startswith(("server.", "sched.", "step.")) \
                and s.parent_id:
            p = by_id[s.parent_id]
            assert p.t_start <= s.t_start <= s.t_end <= p.t_end
    assert validate_trace(tracer.spans) == []
    assert engine.decode_compile_count == 1
    # the window's one compile is marked on the tracer's clock, after
    # the window that traced it; the adapter adds no window span
    (compile,) = tracer.find("xla.compile")
    assert compile.attrs["count"] == 1
    assert compile.t_start >= tracer.find("step.window")[0].t_end
    assert not tracer.find("decode.window")


def test_default_tracer_records_nothing(lm):
    _, sess, out = _serve(lm, None, n=4)
    assert len(out) == 4
    assert sess.tracer is NULL_TRACER and NULL_TRACER.spans == []
