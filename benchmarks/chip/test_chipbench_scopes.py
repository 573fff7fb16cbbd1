"""The mesh program's named scopes and the split that reads them: the
scopes reach the HLO's ``op_name`` metadata, the six ppermutes of a round
carry the bytes of the exchange model, a hand-worked trace and a
recorded one split into their per-scope sums, and ``scope_split.py``
keeps the program it traced."""

import json
from pathlib import Path

import pytest

import testlib_chipbench as lib
import scope_split
from chipbench import scopes, tracered

DATA = Path(__file__).resolve().parent / "testdata"
SCOPES = ("sfc.pack", "sfc.unpack", "sfc.shell")

MESH_PROGRAM = r"""
import json, re
import jax, jax.numpy as jnp
from repro.core.layout import store_spec
from repro.stencil import DistributedPipeline, make_stencil_mesh

pipe = DistributedPipeline(mesh=make_stencil_mesh((2, 2, 1)), spec=store_spec("hilbert", 8),
                           M=16, T=8, g=1, S=4, rule="jacobi")
state = jax.ShapeDtypeStruct((2, 2, 1, 16 ** 3), jnp.float32)
run = pipe.run_fn(8)
lowered = run.lower(state)

def scopes(names):
    return sorted({p for n in names for p in n.split("/") if p.startswith("sfc.")})

def ppermutes(jaxpr, rounds=None):
    # (trip count of the loop around it, operand) of each ppermute
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "ppermute":
            yield rounds, eqn.invars[0].aval
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from ppermutes(sub, eqn.params.get("length", rounds))

sent = list(ppermutes(jax.make_jaxpr(run)(state).jaxpr))
print(json.dumps({
    "lowered": scopes(re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True))),
    "compiled": scopes(re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())),
    "rounds": [n for n, _ in sent],
    "round_bytes": sum(a.size * a.dtype.itemsize for _, a in sent),
    "model_bytes_per_step": pipe.exchange_bytes_per_step(),
}))
"""


@pytest.fixture(scope="module")
def mesh_program():
    out = lib.run_four_devices(MESH_PROGRAM)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_mesh_program_carries_its_scopes(mesh_program):
    """Every scope is in the program as lowered (its locations) and on
    the compiled program's ops."""
    assert mesh_program["lowered"] == sorted(SCOPES)
    assert mesh_program["compiled"] == sorted(SCOPES)


def test_ppermute_bytes_are_the_exchange_model(mesh_program):
    """One round's six ppermutes, in the body of the loop (``fori_loop``,
    a scan of two rounds of S=4), times the two rounds is what the model
    says eight steps exchange."""
    assert mesh_program["rounds"] == [2] * 6
    assert 2 * mesh_program["round_bytes"] == mesh_program["model_bytes_per_step"] * 8
    assert mesh_program["round_bytes"] == 4 * 2 * (4 * 16 * 16 + 4 * 24 * 16 + 4 * 24 * 24)


HLO = """HloModule jit_local_run, entry_computation_layout={(f32[1,1,1,4096]{3,2,1,0})->f32[1,1,1,4096]{3,2,1,0}}

%body (p: (s32[], f32[8,8,8,8])) -> (s32[], f32[8,8,8,8]) {
  %gather_fusion.1 = f32[1,1024]{1,0:T(1,128)} fusion(%p), kind=kLoop, calls=%fc.1, metadata={op_name="jit(local_run)/shard_map/while/body/sfc.pack/jit(_take)/gather" stack_frame_id=3}
  %collective-permute-start = (f32[1,1024]{1,0}, f32[1,1024]{1,0}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%gather_fusion.1), channel_id=1, source_target_pairs={{0,1},{1,0}}, metadata={op_name="jit(local_run)/shard_map/while/body/ppermute"}
  %collective-permute-done = f32[1,1024]{1,0:T(1,128)} collective-permute-done(%collective-permute-start), metadata={op_name="jit(local_run)/shard_map/while/body/ppermute"}
  %scatter_fusion.2 = f32[1,4,16,16]{3,2,1,0} fusion(%collective-permute-done), kind=kLoop, calls=%fc.2, metadata={op_name="jit(local_run)/shard_map/while/body/sfc.shell/sfc.unpack/scatter"}
  %fusion.3 = f32[9728]{0} fusion(%scatter_fusion.2), kind=kLoop, calls=%fc.3, metadata={op_name="jit(local_run)/shard_map/while/body/sfc.shell/concatenate;jit(local_run)/shard_map/while/body/sfc.pack/concatenate"}
  %copy.9 = f32[8,8,8,8]{3,2,1,0} copy(%p)
  %all-reduce.1 = bf16[2,3]{1,0} all-reduce(%x), replica_groups={}, to_apply=%add
  ROOT %stencil_step_fused.1 = f32[8,8,8,8]{3,2,1,0} custom-call(%copy.9, %fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(local_run)/shard_map/while/body/pallas_call"}
}
"""


def test_hlo_ops_read_scopes_and_payloads():
    ops = scopes.hlo_ops(HLO)
    assert ops["gather_fusion.1"] == ("sfc.pack", 0.0)
    assert ops["collective-permute-start"] == ("", 0.0)      # counted at its -done
    assert ops["collective-permute-done"] == ("", 4096.0)
    assert ops["scatter_fusion.2"] == ("sfc.unpack", 0.0)     # the innermost scope
    assert ops["fusion.3"] == ("sfc.shell", 0.0)              # merged metadata: the first
    assert ops["copy.9"] == ("", 0.0)
    assert ops["all-reduce.1"] == ("", 12.0)
    assert ops["stencil_step_fused.1"] == ("", 0.0)


def _ev(plane, name, start, dur, hlo=scopes.hlo_ops(HLO)):
    if "device" not in plane:
        return tracered.Event(plane, "python", name, start, dur)
    return scopes.ScopedEvent(plane, "XLA Ops", name, start, dur, *hlo.get(name, ("", 0.0)))


def test_hand_worked_scopes_and_bytes():
    """Two devices in a 10 us window, ops named as in ``HLO``.

    Device 0: a 1.0 us pack gather; an async permute whose start (0.1 us)
    and done (0.2 us) straddle a 0.5 us unpack that the done overlaps by
    0.1 us; a 0.4 us shell fusion; a 0.3 us unscoped copy; the kernel
    5 us; a 0.2 us all-reduce. Device 1: a while holding a 2 us pack
    gather, the done (0.3 us), the 6 us kernel and a 0.5 us copy, and a
    pack gather that began 1 us before the window.
    """
    d0, d1 = "/device:TPU:0", "/device:TPU:1"
    events = [
        _ev("/host:CPU", "window", 1000, 10000),
        _ev(d0, "gather_fusion.1", 1000, 1000),
        _ev(d0, "collective-permute-start", 2000, 100),
        _ev(d0, "scatter_fusion.2", 2200, 500),
        _ev(d0, "collective-permute-done", 2600, 200),
        _ev(d0, "fusion.3", 3000, 400),
        _ev(d0, "copy.9", 3500, 300),
        _ev(d0, "stencil_step_fused.1", 4000, 5000),
        _ev(d0, "all-reduce.1", 9000, 200),
        _ev(d1, "gather_fusion.1", 0, 2000),
        _ev(d1, "while", 2000, 9000),
        _ev(d1, "gather_fusion.1", 2000, 2000),
        _ev(d1, "collective-permute-done", 4000, 300),
        _ev(d1, "stencil_step_fused.1", 4300, 6000),
        _ev(d1, "copy.9", 10300, 500),
    ]
    r = scopes.split(events, "stencil_step_fused")
    ns = 1e-9
    assert r.scope_s == {
        "sfc.pack": pytest.approx((1000 + 1000 + 2000) / 2 * ns),
        "sfc.unpack": pytest.approx(400 / 2 * ns),
        "sfc.shell": pytest.approx(400 / 2 * ns),
        "": pytest.approx((300 + 500) / 2 * ns)}
    assert sum(r.scope_s.values()) == pytest.approx(r.trace.other_s)
    assert r.collective_bytes == pytest.approx((4096 + 12 + 4096) / 2)


def test_op_of_no_duration_holds_nothing():
    """The profiler records a ``custom-call`` of no duration at the very
    start of a real op; ``tracered.leaves`` takes the pair for a loop
    and drops the real op, the split leaves the empty one out first."""
    d0 = "/device:TPU:0"
    events = [_ev("/host:CPU", "window", 0, 1000),
              _ev(d0, "custom-call.7", 100, 0),
              _ev(d0, "gather_fusion.1", 100, 300),
              _ev(d0, "copy.9", 500, 100)]
    assert tracered.reduce_events(events, "stencil_step_fused").other_s == pytest.approx(100e-9)
    r = scopes.split(events, "stencil_step_fused")
    assert r.trace.other_s == pytest.approx(400e-9)
    assert r.scope_s == {"sfc.pack": pytest.approx(300e-9), "": pytest.approx(100e-9)}


def test_scope_of_takes_the_innermost():
    assert scopes.scope_of("jit(run)/sfc.shell/sfc.pack/jit(_take)/gather") == "sfc.pack"
    assert scopes.scope_of("jit(run)/while/body/copy") == ""
    assert scopes.scope_of("") == ""


def _xplane(tmp_path) -> Path:
    """A device plane as a TPU's profile holds it: ops named by their HLO
    text, and the program's executions on the ``XLA Modules`` line. The
    copy runs outside them, as the harness's probe does."""
    from jax.profiler import ProfileData

    text = """
    planes { id: 1 name: "/host:CPU"
      lines { id: 1 name: "python" timestamp_ns: 1000
        events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 } }
      event_metadata { key: 1 value { id: 1 name: "window" } } }
    planes { id: 2 name: "/device:TPU:0"
      lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
        events { metadata_id: 4 offset_ps: 0 duration_ps: 2000000 } }
      lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
        events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
        events { metadata_id: 2 offset_ps: 1000000 duration_ps: 200000 }
        events { metadata_id: 3 offset_ps: 2500000 duration_ps: 500000 } }
      event_metadata { key: 1 value { id: 1 name: "%gather_fusion.1 = f32[1,1024]{1,0} fusion(%p)" } }
      event_metadata { key: 2 value { id: 2 name: "%collective-permute-done = f32[1,1024]{1,0} collective-permute-done(%s)" } }
      event_metadata { key: 3 value { id: 3 name: "%fusion.3 = f32[1]{0} fusion(%x)" } }
      event_metadata { key: 4 value { id: 4 name: "jit_local_run(123)" } } }
    """
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return path


def test_load_joins_the_program(tmp_path):
    by_name = {e.name: e for e in scopes.load(_xplane(tmp_path), HLO)}
    assert set(by_name) == {"window", "gather_fusion.1", "collective-permute-done", "fusion.3"}
    assert (by_name["gather_fusion.1"].scope, by_name["gather_fusion.1"].bytes) == ("sfc.pack", 0)
    assert by_name["collective-permute-done"].bytes == 4096
    assert not isinstance(by_name["fusion.3"], scopes.ScopedEvent)  # ran outside the program
    assert not any(isinstance(e, scopes.ScopedEvent)
                   for e in scopes.load(_xplane(tmp_path), ""))


def test_recorded_mesh_trace_scopes():
    """testdata/trace_mesh_scoped_excerpt.json: one call of one chip of a
    recorded scoped jacobi-mesh2x2 window (see its note)."""
    raw = json.loads((DATA / "trace_mesh_scoped_excerpt.json").read_text())
    r = scopes.split([(scopes.ScopedEvent if "scope" in e else tracered.Event)(**e)
                      for e in raw["events"]], "stencil_step_fused")
    want = raw["hand_worked"]
    assert r.trace.devices == 1
    assert r.scope_s == pytest.approx(want["scope_s"])
    assert r.collective_bytes == pytest.approx(want["collective_bytes"])
    assert r.trace.other_s == pytest.approx(sum(want["scope_s"].values()))
    assert r.trace.kernel_s == pytest.approx(want["kernel_s"])
    assert r.trace.collective_s == pytest.approx(want["collective_s"])


def test_scope_split_keeps_the_traced_program(tmp_path):
    """A traced run of a small one-chip cell on the CPU: the result line
    is the harness's, with the program's HLO text kept beside the
    profile and the split's numbers added."""
    cell = lib.small_cell("jacobi1024.steady")
    r = scope_split.run(cell, 2 ** 31 + 13, 0.2, str(tmp_path))
    assert r["correct"] and "breakdown" in r
    hlo = (tmp_path / scope_split.HLO_FILE).read_text()
    assert hlo.startswith("HloModule ")
    assert cell.driver().Driver.compile.__name__ == "compile"
    assert {"nonkernel_device_ms_per_step", "exchange_mb_per_step"} <= set(r["scopes"])
    assert r["scopes"]["exchange_mb_per_step"] == 0
