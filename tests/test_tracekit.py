"""TraceKit: tracer span nesting (incl. across threads), exporter
validity/round-trip, metric instrument semantics, the StepEmitter stdout
contract, the disabled-tracer overhead bound, serve-side bit-identical
token streams tracer on vs off, the compile-skipping ms_per_step EMA,
nested stats() sections + deprecated flat aliases, the BlockLLM
selection-telemetry helpers, and the train step's spans (nesting, the
profiler's host plane, compile spans, bit-identical training)."""
import io
import json
import threading
import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import selection as sel
from repro.models import model
from repro.obs import (Counter, Gauge, Histogram, MetricsRegistry,
                       StepEmitter, Tracer, chrome_trace_dict,
                       load_trace_file, write_trace)
from repro.runtime.serve_loop import DecodeServer, Request

K = jax.random.PRNGKey


# ---------------------------------------------------------------- tracer


def test_span_nesting_parent_ids():
    tr = Tracer()
    with tr.span("outer", lane="L") as outer:
        with tr.span("inner", lane="L") as inner:
            pass
        with tr.span("inner2", lane="L"):
            pass
    with tr.span("sibling", lane="L"):
        pass
    by_name = {e.name: e for e in tr.events()}
    assert by_name["outer"].parent_id is None
    assert by_name["inner"].parent_id == outer.span_id
    assert by_name["inner2"].parent_id == outer.span_id
    assert by_name["sibling"].parent_id is None
    assert by_name["inner"].span_id == inner.span_id
    for e in tr.events():
        assert e.t1_ns >= e.t0_ns


def test_span_nesting_is_per_thread():
    """Spans opened on different threads never adopt each other as
    parents; the default lane is the thread name."""
    tr = Tracer()
    barrier = threading.Barrier(2)

    def work(tag):
        with tr.span(f"outer_{tag}"):
            barrier.wait()           # both outers open simultaneously
            with tr.span(f"inner_{tag}"):
                pass

    ts = [threading.Thread(target=work, args=(i,), name=f"w{i}")
          for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    by_name = {e.name: e for e in tr.events()}
    assert len(tr) == 4
    for i in range(2):
        outer, inner = by_name[f"outer_{i}"], by_name[f"inner_{i}"]
        assert outer.parent_id is None
        assert inner.parent_id == outer.span_id     # never cross-thread
        assert outer.lane == inner.lane == f"w{i}"


def test_retroactive_span_and_instant():
    tr = Tracer()
    t0 = Tracer.now()
    time.sleep(0.001)
    with tr.span("open", lane="L"):
        tr.add_span("queue_wait", t0, Tracer.now(), lane="q", rid=7)
        tr.instant("mark", lane="L", step=3)
    qs = tr.spans("queue_wait")[0]
    assert qs.parent_id is None          # retroactive: not on the stack
    assert qs.args == {"rid": 7}
    assert qs.dur_ns > 0
    inst = [e for e in tr.events() if e.kind == "instant"][0]
    assert inst.name == "mark" and inst.args["step"] == 3
    assert set(tr.lanes()) == {"L", "q"}


# -------------------------------------------------------------- exporters


def _demo_tracer():
    tr, reg = Tracer(), MetricsRegistry()
    with tr.span("request", lane="tenant:base", rid=0):
        with tr.span("prefill", lane="tenant:base", chunk=8):
            pass
        tr.instant("jit_compile", lane="sched")
    tr.add_span("queue_wait", tr.t_origin_ns, Tracer.now(), lane="sched",
                arr=np.arange(2))        # non-jsonable arg -> str()
    reg.counter("decode/steps").inc(5)
    reg.gauge("sched/queue_depth").set(2)
    reg.histogram("decode/step_ms").observe(1.5)
    return tr, reg


def test_chrome_trace_schema_and_monotonic_lanes():
    tr, reg = _demo_tracer()
    obj = chrome_trace_dict(tr, reg)
    json.dumps(obj)                       # fully serializable
    evs = obj["traceEvents"]
    lanes_named = {(e["pid"], e["tid"]) for e in evs
                   if e["ph"] == "M" and e["name"] == "thread_name"}
    last = {}
    for e in evs:
        assert {"name", "ph", "pid", "tid"} <= set(e)
        if e["ph"] == "M":
            continue
        lane = (e["pid"], e["tid"])
        assert lane in lanes_named
        assert e["ts"] >= last.get(lane, float("-inf"))
        last[lane] = e["ts"]
        if e["ph"] == "X":
            assert e["dur"] >= 0
    # span args survive, with parent/id attached
    pf = [e for e in evs if e["name"] == "prefill"][0]
    assert pf["args"]["chunk"] == 8 and "parent" in pf["args"]
    assert obj["metrics"]["decode/steps"] == 5


def test_exporter_round_trip(tmp_path):
    tr, reg = _demo_tracer()
    pj = write_trace(tmp_path / "t.jsonl", tr, reg)
    recs = load_trace_file(pj)
    assert recs[0] == {"kind": "header", "format": "tracekit.v1",
                       "clock": "monotonic_us"}
    spans = [r for r in recs if r.get("kind") == "span"]
    assert {s["name"] for s in spans} == {"request", "prefill",
                                          "queue_wait"}
    for s in spans:
        assert s["dur_us"] >= 0 and "lane" in s and "ts_us" in s
    req = [s for s in spans if s["name"] == "request"][0]
    pf = [s for s in spans if s["name"] == "prefill"][0]
    assert pf["parent"] == req["id"]
    # the non-jsonable numpy arg was coerced to a string
    qw = [s for s in spans if s["name"] == "queue_wait"][0]
    assert isinstance(qw["args"]["arr"], str)
    mets = {r["name"]: r["value"] for r in recs
            if r.get("kind") == "metric"}
    assert mets["decode/steps"] == 5
    assert mets["decode/step_ms"]["count"] == 1
    # extension dispatch: anything not .jsonl is Chrome format
    pc = write_trace(tmp_path / "t.json", tr, reg)
    evs = load_trace_file(pc)
    assert any(e["name"] == "thread_name" for e in evs)


# ---------------------------------------------------------------- metrics


def test_metric_instrument_semantics():
    reg = MetricsRegistry()
    c = reg.counter("a/n")
    c.inc()
    c.inc(4)
    assert reg.counter("a/n") is c and c.value == 5
    g = reg.gauge("a/g")
    g.set(3)
    g.set(1.5)
    assert g.value == 1.5
    h = reg.histogram("a/h")
    for v in (1.0, 3.0, 2.0):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 3 and s["sum"] == 6.0 and s["min"] == 1.0 \
        and s["max"] == 3.0 and s["p50"] == 2.0
    with pytest.raises(TypeError):
        reg.gauge("a/n")                  # kind mismatch on reuse
    nested = reg.nested()
    assert nested["a"]["n"] == 5 and nested["a"]["g"] == 1.5
    txt = reg.dump_text()
    assert "a/n 5" in txt and "a/h.count 3" in txt


def test_histogram_decimation_bounds_memory():
    h = Histogram("h", cap=64)
    n = 10_000
    for i in range(n):
        h.observe(float(i))
    assert h.count == n and h.min == 0.0 and h.max == float(n - 1)
    assert h.sum == sum(range(n))
    assert len(h._samples) < 64           # buffer stayed bounded
    # percentiles still representative of the full run (not the tail)
    assert h.percentile(50) == pytest.approx(n / 2, rel=0.1)
    assert h.percentile(99) >= 0.9 * n


# ------------------------------------------------------------ StepEmitter


def test_step_emitter_stdout_contract():
    buf = io.StringIO()
    tr, reg = Tracer(), MetricsRegistry()
    em = StepEmitter(log_every=2, tracer=tr, metrics=reg,
                     metrics_every=0, stream=buf)
    for i in range(1, 5):
        em.on_step(i, {"loss": 1.0 / i, "step": i, "sel_q": 0.05,
                       "ms": 2.0})
    lines = buf.getvalue().splitlines()
    # log_every gates stdout only: 2 lines for 4 steps
    assert len(lines) == 2
    assert lines[0].startswith("step 2: loss=0.5000")
    assert "sel_q=0.05" in lines[0] and "ms=2" in lines[0]
    # ... but the tracer and registry saw every step
    assert len([e for e in tr.events()
                if e.name == "train_step_metrics"]) == 4
    assert reg.counter("train/steps").value == 4
    assert reg.histogram("train/step_ms").count == 4
    assert reg.gauge("train/sel_q").value == 0.05
    em.warn("adapter export skipped: no base", start_step=3)
    assert buf.getvalue().splitlines()[-1] == \
        "warning: adapter export skipped: no base"
    warn = [e for e in tr.events() if e.name == "warning"][0]
    assert warn.args["start_step"] == 3


def test_step_emitter_all_sinks_off_is_silent():
    buf = io.StringIO()
    em = StepEmitter(log_every=0, stream=buf)
    em.on_step(1, {"loss": 0.5})
    assert buf.getvalue() == ""


# ----------------------------------------------------- serve integration


def _serve_cfg(vocab=64):
    return ModelConfig(name="tk", family="dense", num_layers=2,
                       d_model=32, num_heads=4, num_kv_heads=2, d_ff=64,
                       vocab_size=vocab, remat=False)


def _run_serve(cfg, params, tracer=None, metrics=None, n_req=4,
               new_tokens=4, **kw):
    srv = DecodeServer(cfg, params, batch_slots=2, max_seq=32,
                       prefill_chunk=4, tracer=tracer, metrics=metrics,
                       **kw)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 3 + i),
                    max_new_tokens=new_tokens) for i in range(n_req)]
    for r in reqs:
        srv.submit(r)
    srv.run_until_drained()
    return srv, reqs


def test_tracing_does_not_change_token_streams():
    """The acceptance bar: tracer on vs off is bit-identical."""
    cfg = _serve_cfg()
    params = model.init_params(K(0), cfg)
    _, base_reqs = _run_serve(cfg, params, tracer=None)
    tr = Tracer()
    srv, traced_reqs = _run_serve(cfg, params, tracer=tr,
                                  metrics=MetricsRegistry())
    assert {r.rid: tuple(r.out) for r in traced_reqs} == \
           {r.rid: tuple(r.out) for r in base_reqs}
    names = {e.name for e in tr.events()}
    assert {"submit", "queue_wait", "admit", "prefill", "decode_step",
            "request"} <= names
    # every request got a lifecycle span on its tenant lane
    assert len(tr.spans("request")) == len(traced_reqs)
    assert len(tr.spans("queue_wait")) == len(traced_reqs)


def test_ema_skips_compile_steps_and_stats_sections():
    # distinct vocab -> distinct decode-fn shapes -> the first decode
    # step of THIS test compiles even though the lru-cached decode fn
    # was already warmed by other tests in the process
    cfg = _serve_cfg(vocab=80)
    params = model.init_params(K(0), cfg)
    srv, reqs = _run_serve(cfg, params, metrics=MetricsRegistry(),
                           n_req=5, new_tokens=6, ms_per_step="auto")
    # at least the first decode step compiled; compile-laden samples are
    # excluded from both the EMA and the step_ms histogram
    compiles = srv.metrics.counter("sched/compiles").value
    assert compiles >= 1
    assert srv._ms_samples == srv.steps - compiles
    assert srv._ms_samples >= 1
    assert srv.metrics.histogram("decode/step_ms").count == \
        srv._ms_samples
    s = srv.stats()
    # nested sections sourced from the registry
    assert s["decode"]["steps"] == srv.steps
    assert s["sched"]["finished"] == len(reqs)
    assert s["prefill"]["dispatches"] == srv.prefill_dispatches
    # stats schema v2: the flat aliases are gone, the version is stamped
    assert s["stats_version"] == 2
    for gone in ("steps", "swaps", "swap_bytes", "swap_rate", "applied",
                 "prefill_dispatches", "prefill_prompt_tokens",
                 "ms_per_step"):
        assert gone not in s, f"removed flat alias {gone!r} reappeared"


def test_disabled_tracer_overhead_bound():
    """Tracer-off instrumentation is a handful of ``x is None`` guards
    per decode step.  Bound the measured guard cost against 1% of a
    (very conservative) 1ms decode step."""
    tracer = None
    n = 200_000

    t0 = time.perf_counter()
    acc = 0
    for _ in range(n):
        if tracer is not None:            # the exact hot-path guard
            acc += 1
    per_guard_s = (time.perf_counter() - t0) / n
    guards_per_step = 40                  # >> actual count in step()
    assert per_guard_s * guards_per_step < 0.01 * 1e-3, \
        (f"{guards_per_step} guards cost "
         f"{per_guard_s * guards_per_step * 1e6:.2f}us per step "
         f"(>1% of a 1ms decode step)")


# ------------------------------------------------- selection telemetry


def _plan(leaves=(), stacks=()):
    return SimpleNamespace(
        structure=SimpleNamespace(active_leaves=tuple(leaves)),
        stack_idx={sid: np.asarray(idx) for sid, idx in stacks})


def test_plan_churn_jaccard():
    a = _plan(leaves=("w1", "w2"), stacks=[("s", [0, 1])])
    same = _plan(leaves=("w1", "w2"), stacks=[("s", [0, 1])])
    half = _plan(leaves=("w1", "w3"), stacks=[("s", [0, 2])])
    disjoint = _plan(leaves=("w9",), stacks=[("s", [7])])
    assert sel.plan_churn(None, a) == 1.0
    assert sel.plan_churn(a, same) == 0.0
    assert sel.plan_churn(a, disjoint) == 1.0
    # |a| = |half| = 4, overlap = {w1, s/g0} -> 1 - 2/6
    assert sel.plan_churn(a, half) == pytest.approx(1.0 - 2.0 / 6.0)


def test_norm_concentration():
    flat = {f"u{i}": 1.0 for i in range(10)}
    assert sel.norm_concentration(flat, 0.2) == pytest.approx(0.2)
    spiky = {"hot": 10.0, **{f"u{i}": 1e-3 for i in range(9)}}
    assert sel.norm_concentration(spiky, 0.1) > 0.99
    assert sel.norm_concentration({}, 0.5) == 0.0
    # non-finite (optimistic-init) norms are excluded, not propagated
    with_inf = {"a": float("inf"), "b": 3.0, "c": 4.0}
    assert sel.norm_concentration(with_inf, 1.0) == 1.0
    assert 0.0 < sel.norm_concentration(with_inf, 0.5) < 1.0


# --------------------------------------------------- train step spans

STEP_CHILDREN = ("plan", "dispatch", "device_wait", "ingest_norms",
                 "step_metrics")
TRAIN_STEPS = 4


def _tiny_train(tracer, steps=TRAIN_STEPS):
    """A BlockLLM fine-tune of a two-layer model through the train loop;
    returns the losses and the trained rows on the host."""
    from repro import trainers
    from repro.runtime.train_loop import TrainLoopConfig, run
    cfg = ModelConfig(name="tiny-spans", family="dense", num_layers=2,
                      d_model=32, num_heads=2, num_kv_heads=1, d_ff=64,
                      vocab_size=64, remat=False)
    h = trainers.handle("blockllm", cfg, model.init_params(K(0), cfg),
                        sparsity=0.5, k_frac=0.5, patience=100)

    def batch_fn(step):
        t = np.arange(2 * 16).reshape(2, 16) * (step + 3) % 64
        return {"tokens": t.astype(np.int32)}

    out = run(h, batch_fn, TrainLoopConfig(total_steps=steps,
                                           log_every=10 ** 6),
              tracer=tracer)
    assert h.core.tracer is None          # the loop takes its tracer back
    return out["losses"], jax.tree.map(np.asarray, h.state.arrays["sel"])


@pytest.fixture(scope="module")
def traced_train():
    tr = Tracer()
    return tr, _tiny_train(tr)


def _step_of(tr, t_ns):
    """The loop step whose ``train_step`` span holds ``t_ns``."""
    for s in tr.spans("train_step"):
        if s.t0_ns <= t_ns <= s.t1_ns:
            return s.args["step"]
    return None


def test_train_step_children_by_parent_id(traced_train):
    tr, _ = traced_train
    steps = tr.spans("train_step")
    assert [s.args["step"] for s in steps] == list(range(1, TRAIN_STEPS + 1))
    for s in steps:
        kids = [e.name for e in tr.spans() if e.parent_id == s.span_id]
        assert kids == list(STEP_CHILDREN), kids
        assert all(e.lane == "step" for e in tr.spans()
                   if e.parent_id == s.span_id)
    wait = tr.spans("device_wait")
    assert len(wait) == TRAIN_STEPS
    for s, w in zip(steps, wait):
        assert s.t0_ns <= w.t0_ns <= w.t1_ns <= s.t1_ns


def test_tracing_does_not_change_training(traced_train):
    _, (losses, sel_rows) = traced_train
    losses0, sel_rows0 = _tiny_train(None)
    assert losses == losses0
    flat, flat0 = jax.tree.leaves(sel_rows), jax.tree.leaves(sel_rows0)
    assert len(flat) == len(flat0) > 0
    for a, b in zip(flat, flat0):
        assert a.dtype == b.dtype and np.array_equal(a, b)


def test_compile_spans_mark_the_compiling_steps(traced_train):
    tr, _ = traced_train
    comp = tr.spans("compile")
    assert comp and all(e.lane == "compile" for e in comp)
    assert {e.args["event"] for e in comp} <= {"backend_compile",
                                              "cache_retrieval"}
    steps = {_step_of(tr, e.t1_ns) for e in comp}
    # step 1 compiles the mask-refresh step, step 2 the steady one; the
    # repeated steps compile nothing
    assert {1, 2} <= steps
    assert not steps & set(range(3, TRAIN_STEPS + 1))
    # the listener is gone once the loop returned
    n = len(comp)
    jax.jit(lambda x: x * 3 + 1)(np.arange(7.0)).block_until_ready()
    assert len(tr.spans("compile")) == n


def test_profiler_host_plane_holds_the_step_spans(tmp_path):
    from jax.profiler import ProfileData
    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _tiny_train(tr, steps=2)
    finally:
        jax.profiler.stop_trace()
    pd = ProfileData.from_file(str(next(tmp_path.rglob("*.xplane.pb"))))
    host = [ev for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]
    names = [ev.name for ev in host]
    for n in ("data", "train_step") + STEP_CHILDREN:
        assert names.count(n) == 2, n
    # each device_wait lies inside a train_step on the profiler's clock
    steps = [ev for ev in host if ev.name == "train_step"]
    for w in (ev for ev in host if ev.name == "device_wait"):
        assert any(s.start_ns <= w.start_ns and w.end_ns <= s.end_ns
                   for s in steps)
