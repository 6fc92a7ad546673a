"""The program's one span record (``core.profiler``): the ring, ids,
nesting, the compile listener, the profiler's trace, and the names the
model's operations carry on the device."""

import collections
import glob
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import optimizer as opt
from paddle_tpu.core import profiler as P
from paddle_tpu.models import gpt
from paddle_tpu.profiling.steptime import StepTimer


def _named(name, since):
    return [s for s in P.spans(since) if s[0] == name]


def test_ring_is_bounded_and_spans_is_a_snapshot():
    since = time.time_ns()
    for i in range(P.RING + 100):
        P.record_span("t.fill", since + i, 1, i=i)
    snap = P.spans()
    assert len(snap) == P.RING                  # the oldest fell out
    assert snap[-1][4] == {"i": P.RING + 99}
    with P.record_event("t.later"):
        pass
    assert len(snap) == P.RING and snap[-1][0] == "t.fill"   # a copy
    assert P.spans()[-1][0] == "t.later"
    # since_ns filters on the start
    assert [s[0] for s in P.spans(time.time_ns())] == []


def test_nested_spans_share_a_thread_and_give_self_time():
    since = time.time_ns()
    with P.record_event("t.outer"):
        time.sleep(0.02)
        with P.record_event("t.inner"):
            time.sleep(0.03)
    (outer,), (inner,) = _named("t.outer", since), _named("t.inner", since)
    assert outer[3] == inner[3] == threading.get_ident()
    # the child ended first and lies inside its parent
    names = [s[0] for s in P.spans(since)]
    assert names.index("t.inner") < names.index("t.outer")
    assert outer[1] <= inner[1] and inner[1] + inner[2] <= outer[1] + outer[2] + 50_000
    own = outer[2] - inner[2]                   # self time: less the child
    assert 15e6 < own < outer[2] and inner[2] >= 29e6


def test_ids_may_be_filled_in_while_the_span_is_open():
    since = time.time_ns()
    with P.record_event("t.ids", req="abc", dispatch=7) as span:
        span.ids["rows"] = 13
    (s,) = _named("t.ids", since)
    assert s[4] == {"req": "abc", "dispatch": 7, "rows": 13}


def test_record_span_takes_a_start_known_only_at_the_end():
    since = time.time_ns()
    P.record_span("t.late", since + 5, 1234, thread=42, req="r1")
    (s,) = _named("t.late", since)
    assert s[1:] == (since + 5, 1234, 42, {"req": "r1"})
    P.record_span("t.mine", since + 6, 1)
    assert _named("t.mine", since)[0][3] == threading.get_ident()


def test_a_span_on_another_thread_carries_that_thread():
    since = time.time_ns()

    def work():
        with P.record_event("t.worker"):
            pass

    th = threading.Thread(target=work)
    th.start()
    th.join(timeout=10)
    assert not th.is_alive()
    (s,) = _named("t.worker", since)
    assert s[3] == th.ident != threading.get_ident()


def test_concurrent_writers_lose_no_span():
    since = time.time_ns()
    n, workers = 300, 8

    def work(k):
        for i in range(n):
            with P.record_event("t.stress", k=k, i=i):
                pass

    threads = [threading.Thread(target=work, args=(k,)) for k in range(workers)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=30)
    assert not any(th.is_alive() for th in threads)
    got = collections.Counter(s[4]["k"] for s in _named("t.stress", since))
    assert got == {k: n for k in range(workers)}


def test_the_compile_listener_records_jax_compile_under_the_open_span():
    since = time.time_ns()
    with P.record_event("t.compiles"):
        # a shape and a constant no other test compiles
        jax.jit(lambda a: a * 3.25 + 17)(jnp.ones((3, 5, 7))).block_until_ready()
    (outer,) = _named("t.compiles", since)
    inside = [s for s in P.spans(since) if s[0].startswith("jax.")
              and s[3] == outer[3]
              and outer[1] <= s[1] + 1_000_000
              and s[1] + s[2] <= outer[1] + outer[2] + 1_000_000]
    names = {s[0] for s in inside}
    assert {"jax.trace", "jax.lower", "jax.compile"} <= names
    compile_ = [s for s in inside if s[0] == "jax.compile"][-1]
    assert compile_[2] > 0
    assert compile_[4]["event"] == "/jax/core/compile/backend_compile_duration"
    assert "fun" in compile_[4]


def test_record_event_in_an_open_session_is_in_the_xplane_under_its_name(tmp_path):
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with P.record_event("t.traced", req="r9", dispatch=3):
            time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    found = [(e, dict(e.stats)) for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name == "t.traced"]
    assert len(found) == 1
    event, stats = found[0]
    assert event.duration_ns >= 10e6
    assert stats["req"] == "r9" and int(stats["dispatch"]) == 3


def test_table_and_chrome_dump_are_views_of_the_ring(tmp_path):
    import json

    with P.record_event("t.before"):
        pass
    P.enable_profiler()
    with P.record_event("t.step", n=1):
        with P.record_event("t.fwd"):
            pass
    with P.record_event("t.step", n=2):
        pass
    rows = {r["name"]: r for r in P.disable_profiler(print_table=False)}
    with P.record_event("t.after"):
        pass
    assert set(rows) >= {"t.step", "t.fwd"} and rows["t.step"]["calls"] == 2
    assert "t.before" not in rows and "t.after" not in rows
    path = str(tmp_path / "tl.json")
    n = P.timeline(path, extra_spans=[("x[1]", 1.0, 2.0, 1)])
    events = json.load(open(path))["traceEvents"]
    ours = [e for e in events if e["name"].startswith("t.")]
    assert n == len(events) and len(ours) == 3
    assert {e["name"] for e in events} >= {"t.step", "t.fwd", "x[1]"}
    assert [e["args"] for e in ours if e["name"] == "t.step"] == [{"n": 1}, {"n": 2}]
    assert not hasattr(P, "_events") and not hasattr(P, "_spans")


def _tiny_gpt_trainer(**cfg):
    conf = gpt.base_config(vocab_size=512, max_len=64, d_model=64, d_inner=128,
                           num_heads=4, num_layers=2, use_flash=False,
                           fused_ce=True, ce_chunk=128, **cfg)
    tr = pt.Trainer(pt.build(gpt.make_model(conf)), opt.AdamW(3e-4),
                    loss_name="loss")
    feed = {"ids": np.ones((2, 64), np.int32),
            "labels": np.ones((2, 64), np.int32)}
    return tr, feed


def test_trainer_spans_are_in_the_ring_and_the_timer_reads_them():
    since = time.time_ns()
    tr, feed = _tiny_gpt_trainer()
    tr.startup(sample_feed=feed)
    for _ in range(3):
        out = tr.step(feed)
    jax.block_until_ready(out)
    (startup,) = _named("trainer.startup", since)
    for child in ("trainer.init_params", "trainer.build_step"):
        (c,) = _named(child, since)
        assert startup[1] <= c[1] and c[1] + c[2] <= startup[1] + startup[2] + 50_000
    steps = _named("trainer.step", since)
    assert [s[4]["step"] for s in steps] == [0, 1, 2]
    assert all(s[4]["inst"] == tr.telemetry_inst and s[4]["steps"] == 1
               for s in steps)
    assert len(_named("trainer.put_feed", since)) >= 3
    # the first step compiled, and the ring says under which span
    first = steps[0]
    assert any(s[0] == "jax.compile" and first[1] <= s[1]
               and s[1] + s[2] <= first[1] + first[2] + 1_000_000
               for s in P.spans(since))
    # the timer keeps counters; its spans are the ring's, by inst
    assert not hasattr(tr.step_timer, "_spans")
    assert [n for n, *_ in tr.step_timer.spans_us()] == ["trainer.step[1]"] * 3
    other = StepTimer(inst="someone-else")
    other.record_dispatch(0.0, 1.0)
    assert other.dispatches == 1 and other.spans_us() == []
    tr.reset_profile()
    assert tr.step_timer.spans_us() == []


SCOPES = {"tok", "gpt", "attn", "ffn", "ln", "ce", "optimizer"}
_INSTR = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = \S+ ([a-z\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def test_the_compiled_step_names_its_operations_by_scope():
    """Of the compiled step's instructions that come from the program's
    source (they carry an ``op_name``; what the compiler makes itself,
    constants, tuple plumbing, copies, carries none and no scope can
    name it), at least 95% lie under one of the model's scopes."""
    tr, feed = _tiny_gpt_trainer()
    tr.startup(sample_feed=feed)
    text = tr._step_fn.lower(
        tr.scope.params, tr.scope.opt_state, tr.scope.state,
        jax.random.PRNGKey(0), tr._put_feed(feed), {}).compile().as_text()
    named = under = 0
    seen = set()
    for line in text.splitlines():
        m = _INSTR.match(line)
        op = _OP_NAME.search(line)
        if not m or m.group(1) == "parameter" or not op:
            continue
        named += 1
        parts = set(re.split(r"[^A-Za-z0-9_]+", op.group(1)))
        under += bool(parts & SCOPES)
        seen |= parts & SCOPES
    assert named > 500
    assert under / named >= 0.95, (under, named)
    assert seen == SCOPES


@pytest.mark.parametrize("seq,kernel", [
    ("resident", "flash_fwd"), ("resident", "flash_bwd"),
    ("streamed", "flash_dq"), ("streamed", "flash_dkv")])
def test_the_flash_kernels_are_named(seq, kernel):
    """A call whose sequences sit in VMEM whole runs one backward kernel,
    ``flash_bwd``; one that streams keeps the pair, each by its name."""
    from paddle_tpu.ops import flash_attention as fa

    rows = 128 if seq == "resident" else fa.RESIDENT + 128
    q = jnp.ones((1, 2, rows, 64), jnp.float32)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True).sum()

    jaxpr = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q))
    assert kernel in jaxpr
    names = {"flash_bwd"} if seq == "resident" else {"flash_dq", "flash_dkv"}
    assert {n for n in ("flash_bwd", "flash_dq", "flash_dkv")
            if n in jaxpr} == names
