"""Pin the profiler capture path BEFORE a TPU link window needs it
(round-4 verdict: the watcher's pass 3 had never been proven to emit a
readable trace, risking trace-bug discovery during precious link
minutes). Reference analog: the device tracer -> timeline.py pipeline
(platform/device_tracer.h:49, tools/timeline.py:115) which ships
tested end-to-end.
"""

import glob
import gzip
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))

import trace_summary  # noqa: E402


def _fake_trace():
    # minimal perfetto shape jax.profiler writes: metadata (ph=M)
    # process names + complete (ph=X) duration events, dur in us
    return {"traceEvents": [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "name": "process_name", "pid": 2,
         "args": {"name": "/host:CPU"}},
        {"ph": "X", "pid": 1, "name": "fusion.42", "dur": 3000, "ts": 0},
        {"ph": "X", "pid": 1, "name": "fusion.42", "dur": 1000, "ts": 9},
        {"ph": "X", "pid": 1, "name": "convolution.7", "dur": 2000, "ts": 5},
        {"ph": "X", "pid": 2, "name": "$py_frame_a", "dur": 500, "ts": 0},
        {"ph": "X", "pid": 2, "name": "$py_frame_b", "dur": 700, "ts": 1},
        {"ph": "B", "pid": 1, "name": "not_complete_event", "ts": 2},
    ]}


def test_summarize_ranks_ops_and_buckets_host_frames(capsys):
    trace_summary.summarize(_fake_trace(), top=10)
    out = capsys.readouterr().out
    # busiest lane first, ops ranked by total (fusion 4ms > conv 2ms),
    # $-frames aggregated into one bucket
    tpu_at = out.index("lane: /device:TPU:0")
    cpu_at = out.index("lane: /host:CPU")
    assert tpu_at < cpu_at
    assert out.index("fusion.42") < out.index("convolution.7")
    assert "4.00 ms" in out and "2.00 ms" in out
    assert "[python host frames]" in out
    assert "$py_frame_a" not in out
    assert "not_complete_event" not in out


def test_lane_filter_limits_output(capsys):
    trace_summary.summarize(_fake_trace(), top=10, lane_filter="tpu")
    out = capsys.readouterr().out
    assert "/device:TPU:0" in out and "/host:CPU" not in out


def test_load_trace_missing_dir_exits_with_hint(tmp_path):
    with pytest.raises(SystemExit, match="bench.py --profile"):
        trace_summary.load_trace(str(tmp_path))


def test_load_trace_reads_newest_gz(tmp_path):
    d = tmp_path / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    for name, tag in [("old.trace.json.gz", "old"),
                      ("new.trace.json.gz", "new")]:
        with gzip.open(d / name, "wt") as f:
            json.dump({"traceEvents": [], "tag": tag}, f)
        os.utime(d / name, (1, 1) if tag == "old" else None)
    assert trace_summary.load_trace(str(tmp_path))["tag"] == "new"


@pytest.mark.slow
def test_bench_profile_emits_readable_trace(tmp_path):
    """End-to-end: bench.py --profile materializes a *.trace.json.gz
    that trace_summary can parse."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py"), "--model",
         "mnist_mlp", "--quick", "--profile", str(tmp_path)],
        capture_output=True, text=True, timeout=420, env=env, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    row = json.loads(r.stdout.strip().splitlines()[-1])
    assert row["value"] > 0
    gzs = glob.glob(str(tmp_path / "**" / "*.trace.json.gz"),
                    recursive=True)
    assert gzs, f"no trace under {tmp_path}"
    trace = trace_summary.load_trace(str(tmp_path))
    assert any(e.get("ph") == "X" and "dur" in e
               for e in trace["traceEvents"])
