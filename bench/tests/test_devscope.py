"""Device time by engine phase and idle gaps named by the program's
spans (``bench.devscope``): known answers on a hand-made record, and on
a slice of a scoped chip trace (``data/trace_scoped.json``) against an
independent sweep.  The reduction ``bench.devtrace`` makes of the same
ops is unchanged by the extra field."""

import json
import os
from collections import defaultdict

import pytest

from bench import devscope, devtrace

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_scoped.json")


def hand_made():
    cc, fu = "custom-call", "fusion"
    return {"devices": {"0": [
        [0, 10, fu, "%f.1 = s32[] fusion()", "engine.level_append"],
        [10, 20, cc, "%compat_join_pairs.2 = s32[] custom-call()",
         "engine.level_join"],
        [30, 5, fu, "%f.3 = s32[] fusion()", "engine.level_join"],
        [35, 5, fu, "%f.4 = s32[] fusion()", "engine.level_recon"],
        [50, 10, cc, "%compat_join_pairs.5 = s32[] custom-call()",
         "engine.l0_join"],
        [60, 10, fu, "%f.6 = s32[] fusion()", "engine.expire"],
        [70, 4, fu, "%copy.7 = s32[] fusion()", None],
        [90, 5, fu, "%f.8 = s32[] fusion()", "engine.emit"]]},
        "host": [[0, 100, "bench.window"],
                 [0, 100, "repro.serve.round"],
                 [0, 80, "repro.tick"],
                 [38, 14, "repro.tick.barrier"],
                 [80, 8, "repro.ingest.pump"]]}


def test_hand_made_record():
    rec = hand_made()
    split = devscope.by_scope_ns(rec)
    assert split["engine.level_join"] == {"custom-call": 20, "other": 5}
    assert split[None] == {"custom-call": 0, "other": 4}
    ph = devscope.phases_ms_per_tick(split, 2)
    assert ph["kernel.level_join_ms_per_tick"] == 10e-6
    assert ph["kernel.l0_join_ms_per_tick"] == 5e-6
    assert ph["engine.join_prep_ms_per_tick"] == 2.5e-6
    assert ph["engine.append_ms_per_tick"] == 5e-6
    assert ph["engine.compact_ms_per_tick"] == 2.5e-6
    # idle: [40, 50) [74, 90) [95, 100) = 31; the program spans below
    # the round cover [40, 50), [74, 80) and [80, 88): 24 of it
    assert devscope.idle_unattributed_share(rec) == pytest.approx(7 / 31)
    s = devscope.summarize(rec, 2)
    assert s["kernel_ms_per_tick"] == 15e-6
    assert s["device_ops"][0] == ["custom-call:compat_join_pairs.2",
                                  "engine.level_join", 20e-9]
    # the innermost span over half of a gap names it: [74, 90) lies
    # half in the pump, the rest in the tick and the round's own code
    assert [g[0] for g in s["idle_gaps"]] == [
        "serve.round (100% of the gap)", "tick.barrier (100% of the gap)",
        "serve.round (100% of the gap)"]
    no_round = dict(rec, host=[h for h in rec["host"]
                               if h[2] != "repro.serve.round"])
    assert devscope.summarize(no_round, 2)["idle_gaps"][0][0] \
        == "ingest.pump (50% of the gap)"
    bare = {"devices": rec["devices"], "host": [[0, 100, "bench.window"],
                                                [41, 4, "bench.poll"]]}
    assert [g[0] for g in devscope.summarize(bare, 2)["idle_gaps"]] == [
        "service (not annotated)", "poll (40% of the gap)",
        "service (not annotated)"]
    # devtrace reads the same ops the same way, fifth field or not
    four = {"devices": {"0": [op[:4] for op in rec["devices"]["0"]]},
            "host": rec["host"]}
    assert devtrace.summarize(four)["busy_ns"] == 69


@pytest.mark.skipif(not os.path.exists(DATA), reason="no scoped slice")
def test_scoped_chip_slice():
    with open(DATA) as f:
        rec = json.load(f)
    lo, hi = devtrace.window_of(rec)
    (dev,) = rec["devices"]
    want = defaultdict(int)
    for s, d, opcode, _, scope in rec["devices"][dev]:
        if c := devtrace.clip(s, s + d, lo, hi):
            kind = "custom-call" if opcode == "custom-call" else "other"
            want[(scope, kind)] += c[1] - c[0]
    split = devscope.by_scope_ns(rec)
    assert {(k, kind): v for k, t in split.items() for kind, v in t.items()
            if v} == dict(want)
    # every Pallas kernel in the slice runs under a join scope, by its
    # own name (the compiler's own custom-calls, ConcatBitcast, last
    # nanoseconds and sit in every phase)
    kernels = [op for op in rec["devices"][dev] if op[2] == "custom-call"
               and 'custom_call_target="tpu_custom_call"' in op[3]]
    assert kernels and all(op[4] in ("engine.level_join", "engine.l0_join")
                           for op in kernels)
    assert all(op[3].startswith(("%compat_join_pairs.", "%compat_mask."))
               for op in kernels)
    # the slice's longest gap is named by a program span
    s = devscope.summarize(rec, 1)
    assert not s["idle_gaps"][0][0].startswith("service (not annotated)")
    share = s["idle_unattributed_share"]
    assert share is not None and 0 <= share <= 1
    # and devtrace's reduction of the same ops is what it always was
    four = {"devices": {dev: [op[:4] for op in rec["devices"][dev]]},
            "host": [h for h in rec["host"] if h[2].startswith("bench.")]}
    t = devtrace.summarize(four)
    assert t["by_opcode_ns"]["custom-call"] == want_total(want, "custom-call")


def want_total(want, kind):
    return sum(v for (_, k), v in want.items() if k == kind)


def test_scopes_from_the_hlo_in_a_cpu_profile(tmp_path):
    """The HLO in a real (CPU) profile: the module's instructions carry
    the scopes their ops ran under, and a fusion takes its body's."""
    import glob

    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("engine.level_join"):
            y = jnp.sin(x) * 2
        with jax.named_scope("engine.expire"):
            return jnp.cumsum(y)

    x = jnp.ones((1000,))
    f(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    (pb,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    with open(pb, "rb") as fh:
        hlo = devscope.module_scopes(fh.read())
    (name,) = [k for k in hlo if k.startswith("jit_f(")]
    table = hlo[name]
    assert set(table["ops"].values()) >= {"engine.level_join",
                                          "engine.expire"}
    body = next(c for c, sc in table["comps"].items()
                if sc == "engine.level_join")
    assert devscope.op_scope(
        table, f"%fusion.9 = f32[1000] fusion(%x), calls=%{body}") \
        == "engine.level_join"
