"""The round's stages: the map from compiled instruction to stage, and the
split of a chip's busy time among the stages, without a chip.

One trace is built here with nested operations; a second, recorded on a
v5e over a few rounds of ``ehr-h20-dsgt-q10`` and kept with the stage
map of its compiled round, is under ``testdata/``.
"""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import stages as st  # noqa: E402
import trace_reduce as tm  # noqa: E402

MS = 1_000_000  # ns

_HLO = """\
  %while.2 = (s32[], f32[20,1536]{1,0}) while(%tuple.3), condition=%cond, body=%body, metadata={op_name="jit(round_fn)/fl_local/while" stack_frame_id=2}
  %fusion.125 = f32[20,32]{1,0} fusion(%p.1), kind=kOutput, calls=%fc.1, metadata={op_name="jit(round_fn)/fl_local/while/body/fl_local/transpose(jvp(loss))/dot_general"}
  %gossip_fused_round_gt.1 = (f32[20,1536]{1,0}) custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(round_fn)/fl_wire/jit(_fused_round_gt)/gossip_fused_round_gt/pallas_call"}
  %collective-permute.1 = s8[1,512]{1,0} collective-permute(%q), channel_id=1, source_target_pairs={{0,1},{1,0}}, metadata={op_name="jit(round_fn)/fl_wire/shard_map/fl_transport/ppermute"}
  ROOT %reduce.7 = f32[] reduce(%x, %c), dimensions={0,1}, to_apply=%add, metadata={op_name="jit(round_fn)/fl_metrics/reduce_sum"}
  %copy.42 = f32[20,1536]{1,0} copy(%gte.1)
  %fusion.9 = f32[20]{0} fusion(%gte.2), kind=kLoop, calls=%fc.2, metadata={op_name="jit(round_fn)/fl_wiring/add"}
  %constant.1 = u32[] constant(0)
  %copy.269 = bf16[2,4,8]{2,1,0:T(8,128)(2,1)} copy(%bitcast.6), metadata={op_name="jit(round_fn)/fl_local/convert_element_type"}
  %reshape.940 = bf16[64]{0:T(1024)(128)(2,1)} reshape(%copy.269)
  %tuple.622 = (u32[], bf16[64]{0}) tuple(%constant.1, %reshape.940)
  %while.206 = (u32[], bf16[64]{0}) while(%tuple.622), condition=%wide.cond.22, body=%wide.body.22
"""


def test_stage_map_takes_the_innermost_stage_or_the_operands():
    got = st.stage_map(_HLO)
    assert got == {
        "while.2": "fl_local",
        "fusion.125": "fl_local",
        "gossip_fused_round_gt.1": "fl_wire",
        "collective-permute.1": "fl_transport",  # nested inside fl_wire
        "reduce.7": "fl_metrics",
        "copy.42": st.UNSCOPED,  # no metadata, no operand with a stage
        "fusion.9": st.UNSCOPED,  # a component must match a stage whole
        "constant.1": st.UNSCOPED,
        "copy.269": "fl_local",
        # a layout copy and the loop that runs it, made by the compiler
        # without metadata, take the stage of the value they copy
        "reshape.940": "fl_local",
        "tuple.622": "fl_local",
        "while.206": "fl_local",
    }


def test_stage_map_of_a_compiled_program():
    def f(x):
        with jax.named_scope("fl_local"):
            x, _ = jax.lax.scan(lambda c, _: (jnp.sin(c) * 2.0, None), x,
                                None, length=3)
        with jax.named_scope("fl_wire"):
            with jax.named_scope("fl_transport"):
                y = jnp.roll(x, 1, axis=0)
            x = x + 3.0 * y
        with jax.named_scope("fl_metrics"):
            m = jnp.sum(x * x)
        return x, m

    text = jax.jit(f).lower(jnp.ones((4, 8))).compile().as_text()
    got = st.stage_map(text)
    whiles = [n for n in got if n.startswith("while")]
    assert whiles and all(got[n] == "fl_local" for n in whiles)
    assert {"fl_local", "fl_wire", "fl_metrics"} <= set(got.values())
    # a program without stage scopes maps everything to unscoped
    plain = jax.jit(lambda x: jnp.sum(jnp.sin(x))).lower(
        jnp.ones((4, 8))).compile().as_text()
    assert set(st.stage_map(plain).values()) == {st.UNSCOPED}


def _nested_trace():
    """One chip, a 10 ms window: a ``while`` 0-4 ms holding a fusion 1-2 ms
    and an unscoped copy 2-3 ms; the wire kernel 4-5 ms; a metrics reduce
    6-7 ms partly overlapping an unscoped copy 6.5-8 ms; an op before the
    window is cut off."""
    return {
        "devices": {"0": [
            ["while.2", 0, 4 * MS], ["fusion.125", 1 * MS, 2 * MS],
            ["copy.42", 2 * MS, 3 * MS], ["kernel.1", 4 * MS, 5 * MS],
            ["reduce.7", 6 * MS, 7 * MS], ["copy.50", 6.5 * MS, 8 * MS],
            ["fusion.1", -3 * MS, -1 * MS],
        ]},
        "host": [["bench_window", 0, 10 * MS]],
    }


STAGE_OF = {"while.2": "fl_local", "fusion.125": "fl_metrics",
            "kernel.1": "fl_wire", "reduce.7": "fl_metrics"}


def test_stage_seconds_sum_to_busy_and_innermost_wins():
    tr = _nested_trace()
    (chip,) = tm.reduce(tr, ["kernel.1"])["chips"]
    ev = tm._clip(tr["devices"]["0"], 0, 10 * MS)
    got = st.stage_seconds(ev, STAGE_OF)
    assert sum(got.values()) == pytest.approx(chip["busy_s"])  # 0-5, 6-8
    assert got == pytest.approx({
        # the while's 0-1 and 3-4 ms, and the unscoped copy inside it
        "fl_local": 0.003,
        # the fusion inside the while (innermost), and the reduce until the
        # copy that started later takes over at 6.5 ms
        "fl_metrics": 0.0015,
        "fl_wire": 0.001,
        "unscoped": 0.0015,  # the copy 6.5-8 ms runs inside no operation
    })


def test_stage_seconds_without_stage_map_is_all_unscoped():
    tr = _nested_trace()
    (chip,) = tm.reduce(tr, ["kernel.1"])["chips"]
    ev = tm._clip(tr["devices"]["0"], 0, 10 * MS)
    assert st.stage_seconds(ev, {}) == pytest.approx(
        {"unscoped": chip["busy_s"]})
    assert st.stage_seconds([], STAGE_OF) == {}


def test_recorded_stage_trace():
    rec = json.loads((BENCH / "testdata" / "trace-stages-ehr-h20-dsgt-q10.json")
                     .read_text())
    red = tm.reduce(rec["trace"], rec["kernels"])
    (chip,) = red["chips"]
    assert chip["kernel_events"] == rec["rounds"]
    lo, hi = [(s, e) for n, s, e in rec["trace"]["host"]
              if n == "bench_window"][0]
    (ev,) = [tm._clip(e, lo, hi) for e in rec["trace"]["devices"].values()]
    got = st.stage_seconds(ev, rec["stages"])
    assert sum(got.values()) == pytest.approx(chip["busy_s"], rel=1e-9)
    assert {"fl_local", "fl_wire", "fl_metrics"} <= set(got)
    assert max(got, key=got.get) == "fl_local"
    assert all(rec["stages"][k] == "fl_wire" for k in rec["kernels"])


def test_scope_map_is_inclusive_and_inherits():
    got = st.scope_map(_HLO, ["fl_wire", "fl_transport", "fl_local"])
    assert got["collective-permute.1"] == ("fl_wire", "fl_transport")
    assert got["gossip_fused_round_gt.1"] == ("fl_wire",)
    assert got["fusion.125"] == ("fl_local", "fl_local")
    assert got["reduce.7"] == ()  # fl_metrics was not asked for
    assert got["fusion.9"] == ()  # a component must match a scope whole
    # the compiler's layout copy and its loop take their operand's scopes
    assert got["while.206"] == ("fl_local",)
    # the innermost stage is the last scope of the stages' map
    assert st.stage_map(_HLO) == {
        k: v[-1] if v else st.UNSCOPED
        for k, v in st.scope_map(_HLO, st.STAGES).items()}


def test_scope_seconds_credit_every_enclosing_scope_once():
    """0-4 ms a wire op holding a transport op 1-3 ms; 5-6 ms a metrics op;
    6-7 ms an op without scope inside nothing."""
    ev = [["wire.1", 0, 4 * MS], ["permute.1", 1 * MS, 3 * MS],
          ["metrics.1", 5 * MS, 6 * MS], ["copy.1", 6 * MS, 7 * MS]]
    scopes_of = {"wire.1": ("fl_wire",),
                 "permute.1": ("fl_wire", "fl_transport"),
                 "metrics.1": ("fl_metrics",)}
    got = st.scope_seconds(ev, scopes_of)
    assert got == pytest.approx({"fl_wire": 0.004, "fl_transport": 0.002,
                                 "fl_metrics": 0.001})
    red = tm.reduce({"devices": {"0": ev},
                     "host": [["bench_window", 0, 10 * MS]]}, [],
                    scopes_of=scopes_of)
    assert red["chips"][0]["scope_s"] == pytest.approx(got)


def test_scope_readers_on_the_recorded_trace():
    """The readers of the round's stages on a v5e trace of
    ``ehr-h20-dsgt-q10``: the stage map of its compiled round as scopes."""
    import harness

    rec = json.loads((BENCH / "testdata" / "trace-stages-ehr-h20-dsgt-q10.json")
                     .read_text())
    scopes_of = {k: (v,) if v != st.UNSCOPED else ()
                 for k, v in rec["stages"].items()}
    red = tm.reduce(rec["trace"], rec["kernels"], scopes_of=scopes_of)
    ctx = {"trace": red, "rounds": rec["rounds"]}

    def read(name):
        return harness.load_module(BENCH / "metrics" / f"{name}.py").read(ctx)

    # ms a round, as read by hand from the same trace
    assert read("local_step_ms") == pytest.approx(0.1088984)
    assert read("wire_stage_ms") == pytest.approx(0.0017066)
    assert read("round_metrics_ms") == pytest.approx(0.0018346)
    assert read("transport_ms") is None  # one chip: no exchange between chips
    lo, hi = [(s, e) for n, s, e in rec["trace"]["host"]
              if n == "bench_window"][0]
    (ev,) = [tm._clip(e, lo, hi) for e in rec["trace"]["devices"].values()]
    split = st.stage_seconds(ev, rec["stages"])
    assert read("local_step_ms") == pytest.approx(
        1e3 * split["fl_local"] / rec["rounds"])
