"""The reduction from a trace to metrics, the work functions and the peaks
table, and the run's refusal to measure anywhere but on a known TPU."""
import json
import subprocess
import sys
from pathlib import Path

import pytest

from bench import trace, work
from bench.trace import Event

ROOT = Path(__file__).resolve().parents[1]
H, T0, T1 = trace.HOST_PLANE, "/device:TPU:0", "/device:TPU:1"


def _events():
    """A 100 ns window; TPU:0 busy 10-30 (two overlapping ops) and 60-70,
    TPU:1 busy 0-50; the host in Engine.step over 30-60."""
    return [
        Event(H, "python", "window", 0, 100),
        Event(H, "python", "Engine.step", 30, 30),
        Event(H, "python", "submit", 70, 20),
        Event(T0, trace.OPS_LINE, "_fused_kernel", 10, 15, "jit__stem_cs_call"),
        Event(T0, trace.OPS_LINE, "fusion.1", 20, 10, "jit__stem_cs_call"),
        Event(T0, trace.OPS_LINE, "copy.2", 60, 10, "jit_text_to_words"),
        Event(T0, trace.MODULES_LINE, "jit_text_to_words(1)", 58, 14),
        Event(T1, trace.OPS_LINE, "_fused_kernel", -20, 70, "jit__stem_cs_call"),
    ]


def test_busy_idle_selected_and_breakdown():
    ev = _events()
    assert trace.window(ev) == (0, 100)
    assert trace.devices(ev) == [T0, T1]
    assert trace.busy_ns(ev, T0, 0, 100) == 30       # union, not the sum
    assert trace.busy_ns(ev, T1, 0, 100) == 50       # clipped to the window
    assert trace.idle_share(ev) == pytest.approx(1 - 40 / 100)
    kernel = {"line": trace.OPS_LINE, "pattern": "_fused_kernel"}
    assert trace.selected_ns(ev, kernel) == 15 + 50
    module = {"line": trace.MODULES_LINE, "pattern": "text_to_words"}
    assert trace.selected_ns(ev, module) == 14
    by_module = {"line": trace.OPS_LINE, "pattern": "stem_cs_call"}
    assert trace.selected_ns(ev, by_module) == 15 + 10 + 50
    assert [s.name for s in trace.host_spans(ev, "Engine.step")] == ["Engine.step"]
    top = trace.top_ops(ev)
    assert top[0] == ["jit__stem_cs_call:_fused_kernel", 65e-9]
    gaps = trace.idle_gaps(ev)
    assert gaps[0] == ["Engine.step", 30e-9]        # 30-60, the host stepping
    assert ["none", 10e-9] in gaps                   # 0-10: no span around
    assert ["submit", 30e-9] in gaps                 # 70-100


def test_a_trimmed_chip_trace():
    """25 ms of a newswire.docs window recorded on one TPU v5e: the
    reduction's numbers on it, worked out by hand from its events."""
    ev = trace.load_json(str(ROOT / "bench" / "testdata"
                             / "newswire_docs_trace.json"))
    assert trace.devices(ev) == [T0]
    lo, hi = trace.window(ev)
    assert hi - lo == 25e6
    assert trace.busy_ns(ev, T0, lo, hi) == 2963354.0
    assert trace.idle_share(ev) == pytest.approx(1 - 2963354.0 / 25e6)
    stem = {"line": trace.OPS_LINE, "pattern": r"^%stem_fused_pallas\."}
    assert trace.selected_ns(ev, stem) == 172784.0
    front = {"line": trace.MODULES_LINE, "pattern": "text_to_words"}
    assert trace.selected_ns(ev, front) == 2781714.0
    top = trace.top_ops(ev)
    assert top[0][0] == "jit__text_to_words_jit:%fusion.1 s32[135168]"
    assert any(name == "jit__stem_cs_call:%stem_fused_pallas.1 s32[8,256]"
               for name, _ in top)
    assert trace.idle_gaps(ev)[0][0] == "submit"


def test_short_names():
    assert trace.short_name("%fusion.1 = s32[69632]{0:T(1024)S(1)} fusion("
                            "s32[4096]{0} %x), kind=kCustom") == \
        "%fusion.1 s32[69632]"
    assert trace.short_name("jit__stem_cs_call(13591266251178824936)") == \
        "jit__stem_cs_call"


def test_no_device_op_reads_nothing(tmp_path):
    ev = [Event(H, "python", "window", 0, 100)]
    assert trace.idle_share(ev) is None
    assert trace.selected_ns(ev, {"line": trace.OPS_LINE, "pattern": "x"}) == 0
    path = tmp_path / "t.json"
    trace.save_json(_events(), str(path))
    assert trace.load_json(str(path)) == _events()


def test_xplane_from_a_cpu_profile(tmp_path):
    """The flattening reads the benchmark's host spans from a real
    profiler file; a CPU profile has no TPU plane, so no device op."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones(8)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("Engine.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = trace.load_xplane(str(next(tmp_path.rglob("*.xplane.pb"))))
    lo, hi = trace.window(ev)
    assert hi > lo
    assert len(trace.host_spans(ev, "Engine.step")) == 1
    assert trace.devices(ev) == [] and trace.idle_share(ev) is None


def test_peaks_and_work():
    p = work.peaks("TPU v5 lite")
    assert p["int8_op_per_s"] == 393e12 and p["hbm_byte_per_s"] == 819e9
    with pytest.raises(work.UnknownDevice):
        work.peaks("TPU v9 imaginary")
    job = work.stemmer(256, 1, tri=1000, quad=100, bi=30)
    # 6 tri (10) + 6 quad (7) + infix: 12 tri (10) + 6 bi (5) per word
    assert job.ops == 256 * (6 * (10 + 7) + 6 * (2 * 10 + 5))
    assert job.bytes == 256 * (64 + 20) + 1130 * 4
    share, bound = job.roofline(job.bytes / 819e9, p)
    assert share == pytest.approx(100.0) and bound == "bytes"
    assert work.postings(1024, 1, roots=7).ops == 1024 * 3
    assert work.frontend(100, 10).bytes == 400 + 10 * 72


def test_run_refuses_a_machine_without_a_tpu(tmp_path):
    """No TPU: exit code 1 and nothing on stdout, before any data is
    made or any program built."""
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "newswire.docs", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "HOME": str(tmp_path)})
    assert proc.returncode == 1
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr


def test_run_refuses_an_unknown_device_kind(monkeypatch):
    from bench import run

    class Dev:
        platform, device_kind = "tpu", "TPU v9 imaginary"

    import jax

    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    with pytest.raises(work.UnknownDevice):
        run.find_chips(1)


def test_layer_files_agree_with_the_benchmark():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        layer = json.loads((ROOT / "bench" / "layers" / f"{m['name']}.json")
                           .read_text())
        for key in ("layer", "unit", "moves", "workloads"):
            assert layer[key] == m[key], (m["name"], key)
        assert (ROOT / "bench" / "readers" / f"{layer['reader']}.py").is_file()
    for m in bench["end_to_end"]:
        assert (ROOT / "bench" / "measures" / f"{m['name']}.py").is_file()
