"""Attribution of device time to the program's named scopes
(`scopes.py`): the map from a compiled program's instructions to scopes,
the order in which a call runs them, the naming of a trace's events by
that order, and the per-forward sums the readers take from it, on a small
hand-made program in the form `compiled.as_text()` writes and its events;
and the whole attribution of a record on a program compiled here."""
import pytest

import run
import scopes
import smoke
import trace_reduce

P = "jit(run)/while/body/closed_call"
HLO = f"""HloModule jit_run, is_scheduled=true

%fused_computation.1 (param_0.1: f32[8,32,32,64]) -> f32[8,1,34,34,64] {{
  %param_0.1 = f32[8,32,32,64]{{3,2,1,0}} parameter(0)
  %constant.1 = f32[]{{:T(128)}} constant(0), metadata={{op_name="{P}/stem/stem/jit(_conv_jit)"}}
  %pad.1 = f32[8,34,34,64]{{3,2,1,0}} pad(%param_0.1, %constant.1), padding=0_0x1_1x1_1x0_0, metadata={{op_name="{P}/s0b0/conv1/jit(_conv_jit)/phases/pad" stack_frame_id=3}}
  ROOT %bitcast.1 = f32[8,1,34,34,64]{{4,3,2,1,0}} bitcast(%pad.1), metadata={{op_name="{P}/s0b0/conv1/jit(_conv_jit)/reshape;transpose;reshape"}}
}}

%fused_computation.2 (param_0.2: f32[8,32,32,64], param_1.2: f32[64]) -> f32[8,32,32,64] {{
  %param_0.2 = f32[8,32,32,64]{{3,2,1,0}} parameter(0)
  %param_1.2 = f32[64]{{0}} parameter(1)
  %broadcast.1 = f32[8,32,32,64]{{3,2,1,0}} broadcast(%param_1.2), dimensions={{3}}, metadata={{op_name="{P}/s0b0/bn1/add"}}
  %add.1 = f32[8,32,32,64]{{3,2,1,0}} add(%param_0.2, %broadcast.1), metadata={{op_name="{P}/s0b0/bn1/add"}}
  ROOT %max.1 = f32[8,32,32,64]{{3,2,1,0}} maximum(%add.1, %param_0.2), metadata={{op_name="{P}/s0b0/relu/jit(relu)/max"}}
}}

%body (arg.1: (s32[], f32[8,32,32,64], f32[64])) -> (s32[], f32[8,32,32,64], f32[64]) {{
  %arg.1 = (s32[]{{:T(128)}}, f32[8,32,32,64]{{3,2,1,0}}, f32[64]{{0}}) parameter(0)
  %gte.1 = f32[8,32,32,64]{{3,2,1,0}} get-tuple-element(%arg.1), index=1
  %gte.2 = f32[64]{{0}} get-tuple-element(%arg.1), index=2, metadata={{op_name="{P}/optimization_barrier"}}
  %gte.3 = s32[]{{:T(128)}} get-tuple-element(%arg.1), index=0
  %copy-start.1 = (f32[64]{{0}}, f32[64]{{0}}, u32[]) copy-start(%gte.2)
  %copy.5 = f32[8,32,32,64]{{3,0,2,1}} copy(%gte.1)
  %pad_bitcast_fusion.1 = f32[8,1,34,34,64]{{4,3,2,1,0}} fusion(%copy.5), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{P}/s0b0/conv1/jit(_conv_jit)/reshape;transpose;reshape"}}
  %_conv_jit.7 = f32[8,32,32,64]{{3,2,1,0}} custom-call(%pad_bitcast_fusion.1), custom_call_target="tpu_custom_call", metadata={{op_name="{P}/s0b0/conv1/jit(_conv_jit)/pallas_call" stack_frame_id=9}}, backend_config={{"custom_call_config":{{"body":"TUzv"}}}}
  %copy-done.1 = f32[64]{{0}} copy-done(%copy-start.1)
  %add_maximum_fusion.3 = f32[8,32,32,64]{{3,2,1,0}} fusion(%_conv_jit.7, %copy-done.1), kind=kLoop, calls=%fused_computation.2, metadata={{op_name="{P}/s0b0/relu/jit(relu)/max"}}
  %constant.4 = s32[]{{:T(128)}} constant(1)
  %add.9 = s32[]{{:T(128)}} add(%gte.3, %constant.4), metadata={{op_name="jit(run)/while/body/add"}}
  ROOT %tuple.1 = (s32[]{{:T(128)}}, f32[8,32,32,64]{{3,2,1,0}}, f32[64]{{0}}) tuple(%add.9, %add_maximum_fusion.3, %gte.2)
}}

%cond (arg.2: (s32[], f32[8,32,32,64], f32[64])) -> pred[] {{
  %arg.2 = (s32[]{{:T(128)}}, f32[8,32,32,64]{{3,2,1,0}}, f32[64]{{0}}) parameter(0)
  %gte.4 = s32[]{{:T(128)}} get-tuple-element(%arg.2), index=0
  %constant.2 = s32[]{{:T(128)}} constant(2)
  ROOT %lt.1 = pred[]{{:T(512)}} compare(%gte.4, %constant.2), direction=LT, metadata={{op_name="jit(run)/while/cond/lt"}}
}}

ENTRY %main.1 (x.1: f32[8,32,32,64], b.1: f32[64]) -> f32[8,32,32,64] {{
  %x.1 = f32[8,32,32,64]{{3,2,1,0}} parameter(0), metadata={{op_name="x"}}
  %b.1 = f32[64]{{0}} parameter(1)
  %copy.9 = f32[64]{{0}} copy(%b.1), metadata={{op_name="b"}}
  %constant.3 = s32[]{{:T(128)}} constant(0)
  %tuple.2 = (s32[]{{:T(128)}}, f32[8,32,32,64]{{3,2,1,0}}, f32[64]{{0}}) tuple(%constant.3, %x.1, %copy.9)
  %while.1 = (s32[]{{:T(128)}}, f32[8,32,32,64]{{3,2,1,0}}, f32[64]{{0}}) while(%tuple.2), condition=%cond, body=%body, metadata={{op_name="jit(run)/while"}}
  ROOT %gte.9 = f32[8,32,32,64]{{3,2,1,0}} get-tuple-element(%while.1), index=1, metadata={{op_name="jit(run)/while"}}
}}
"""
# one call of the program: two turns of its loop (two forwards); the
# scalar loop counter and its test have no events
DUR = {"copy.9": 5, "copy-start.1": 1, "copy.5": 20, "pad_bitcast_fusion.1": 40,
       "_conv_jit.7": 200, "copy-done.1": 2, "add_maximum_fusion.3": 30}
TURN = ["copy-start.1", "copy.5", "pad_bitcast_fusion.1", "_conv_jit.7",
        "copy-done.1", "add_maximum_fusion.3"]


def events(turns=2, drop=()):
    """(name, start, dur) of one call, ops back to back inside the loop's
    container event."""
    out, t = [("copy.9", 0, DUR["copy.9"])], 10
    loop_start = t
    for _ in range(turns):
        for n in TURN:
            if n not in drop:
                out.append((n, t, DUR[n]))
            t += DUR[n]
    out.append(("while.1", loop_start, t - loop_start))
    return out


def program():
    return scopes.parse(HLO, loop_trips=2)


def record(evs, prog=None):
    t = {"scopes": scopes.attribute(evs, prog or program(), calls=1),
         "batches": 2}
    return {"kind": "cnn", "mode": "fp32", "trace": t}


def as_traced(evs):
    """The events as a traced run's record keeps them: by op name, the
    instruction's number gone, in order of start."""
    return sorted(((trace_reduce.op_name(n), s, d) for n, s, d in evs),
                  key=lambda e: e[1])


def test_scope_of_drops_what_jax_writes():
    assert scopes.scope_of(
        f"{P}/s1b0/conv1/jit(_conv_jit)/phases/pad") == "s1b0/conv1/phases"
    assert scopes.scope_of(f"{P}/s0b0/conv1/jit(_conv_jit)/reshape;"
                           "transpose;reshape") == "s0b0/conv1"
    assert scopes.scope_of("jit(run)/while/body/add") == ""


def test_a_fusion_goes_to_its_roots_scope():
    p = program()
    # the pad of `phases` is fused under the phase split's reshapes
    assert p["pad_bitcast_fusion.1"]["scope"] == "s0b0/conv1"
    assert p["pad_bitcast_fusion.1"]["spans"] == ["s0b0/conv1",
                                                  "s0b0/conv1/phases"]
    # BN fused under the ReLU that closes it; the shared constant of
    # another scope does not count
    assert p["add_maximum_fusion.3"]["scope"] == "s0b0/relu"
    assert p["add_maximum_fusion.3"]["spans"] == ["s0b0/bn1", "s0b0/relu"]
    assert p["_conv_jit.7"]["spans"] == []


def test_ops_without_an_op_name_take_their_users_scope():
    p = program()
    assert p["copy.5"]["scope"] == "s0b0/conv1"
    # the async copy's start and done feed the BN fusion
    assert p["copy-start.1"]["scope"] == p["copy-done.1"]["scope"] == \
        "s0b0/relu"
    # an argument's layout copy feeds only the loop: no scope
    assert p["copy.9"]["scope"] == ""


def test_runs_per_call_and_no_event_ops():
    p = program()
    assert p["copy.9"]["runs"] == 1 and p["while.1"]["runs"] == 1
    assert p["_conv_jit.7"]["runs"] == 2 and p["copy.5"]["runs"] == 2
    # the loop's condition runs once more than its body; a condition that
    # does vector work would count three events here
    assert scopes.parse(HLO.replace("pred[]{:T(512)} compare",
                                    "pred[8]{0} compare"),
                        loop_trips=2)["lt.1"]["runs"] == 3
    # no events: nothing to do on the device, or a scalar result
    for n in ("gte.1", "constant.4", "tuple.1", "x.1", "gte.9", "add.9",
              "lt.1"):
        assert n not in p


def test_schedule_is_the_order_a_call_runs():
    # the argument's copy, then the loop: its condition (a scalar test, no
    # event) and body in turn
    assert scopes.schedule(HLO, loop_trips=2) == ["copy.9"] + TURN + TURN
    assert scopes.schedule(HLO, loop_trips=3) == ["copy.9"] + TURN * 3


def test_align_names_each_event_by_its_instruction():
    evs = events() + [(n, 1000 + s, d) for n, s, d in events()]
    got = scopes.align(as_traced(evs), scopes.schedule(HLO, 2), calls=2)
    assert got == [e for e in sorted(evs, key=lambda e: e[1])
                   if not e[0].startswith("while")]


def test_align_leaves_an_unplaceable_event_unmapped():
    evs = events()
    evs.insert(3, ("fusion.77", 11, 1))
    got = scopes.align(as_traced(evs), scopes.schedule(HLO, 2), calls=1)
    assert [n for n, _, _ in got].count("") == 1
    # the events after it are named as before
    assert [e for e in got if e[0]] == scopes.align(
        as_traced(events()), scopes.schedule(HLO, 2), calls=1)
    a = scopes.attribute(got, program(), calls=1)
    assert (a["unmapped_n"], a["unmapped_ns"]) == (1, 1)


def test_a_lost_event_shifts_nothing_else():
    evs = events()
    evs.remove(next(e for e in evs if e[0] == "copy.5"))
    got = scopes.align(as_traced(evs), scopes.schedule(HLO, 2), calls=1)
    rows = {(r["scope"], r["op"]): r for r in
            scopes.attribute(got, program(), calls=1)["rows"]}
    assert (rows[("s0b0/conv1", "copy")]["n"],
            rows[("s0b0/conv1", "copy")]["want"]) == (1, 2)
    assert rows[("", "copy")]["n"] == rows[("", "copy")]["want"] == 1
    assert all(r["n"] == r["want"] for k, r in rows.items()
               if k != ("s0b0/conv1", "copy"))


def test_sums_per_forward_and_the_unscoped_remainder():
    a = record(events())["trace"]["scopes"]
    rows = {(r["scope"], r["op"]): r for r in a["rows"]}
    conv = rows[("s0b0/conv1", "_conv_jit")]
    assert (conv["ns"], conv["n"], conv["want"]) == (400, 2, 2)
    table = {s: (tot, own, n) for s, tot, own, n in scopes.by_scope(a, 2)}
    # s0b0: the conv layer (copy, pad fusion, kernel) and the ReLU (the
    # BN fusion and the async copy it waits for)
    assert table["s0b0"][0] == pytest.approx((20 + 40 + 200 + 30 + 3) * 1e-6)
    assert table["s0b0/conv1"][1] == pytest.approx(260e-6)
    assert table[""][1] == pytest.approx(5 / 2 * 1e-6)
    # scopes and the unscoped remainder add up to the busy time: the ops
    # run one after another, and the loop's own event is left out
    own = sum(o for _, _, o, _ in scopes.by_scope(a, 2))
    assert own * 2e6 == pytest.approx(a["events_ns"])
    assert a["events_ns"] == a["busy_ns"] == sum(
        d for n, _, d in events() if n != "while.1")


def test_events_of_another_program_are_unmapped():
    a = record(events() + [("fusion.77", 10_000, 9)])["trace"]["scopes"]
    assert (a["unmapped_n"], a["unmapped_ns"]) == (1, 9)


def test_readers_sum_their_scopes_per_forward():
    rec = record(events())
    # conv glue: the layout copy and the phase fusion, not the kernel
    assert run.read_metric("conv_glue_ms.cnn", rec) == pytest.approx(60e-6)
    # BN and ReLU: the fused BN + ReLU and the async copy of its operand
    assert run.read_metric("bn_act_ms.cnn", rec) == pytest.approx(33e-6)
    assert run.read_metric("quantize_ms.q8", dict(rec, mode="q8")) is None


def test_a_lost_event_fails_the_count():
    rec = record(events(drop=("copy.5",)))
    assert run.read_metric("conv_glue_ms.cnn", rec) is None
    assert run.read_metric("bn_act_ms.cnn", rec) == pytest.approx(33e-6)
    # the wrong number of forwards traced shows as a wrong count too
    rec = record(events(turns=3))
    assert run.read_metric("bn_act_ms.cnn", rec) is None


def test_no_scope_no_reading():
    """A program without the scopes (the program before they were put
    in) reads nothing, and raises nothing."""
    bare = HLO
    for s in ("s0b0/conv1/", "s0b0/bn1/", "s0b0/relu/", "stem/stem/",
              "phases/"):
        bare = bare.replace(s, "")
    rec = record(events(), scopes.parse(bare, loop_trips=2))
    for name in ("conv_glue_ms.cnn", "bn_act_ms.cnn"):
        assert run.read_metric(name, rec) is None
    assert run.read_metric("quantize_ms.q8", dict(rec, mode="q8")) is None


@pytest.mark.parametrize("name", ["conv_glue_ms.cnn", "bn_act_ms.cnn",
                                  "quantize_ms.q8"])
def test_no_trace_no_reading(name):
    assert run.read_metric(name, {"kind": "cnn", "mode": "q8"}) is None
    assert run.read_metric(name, {"kind": "serve"}) is None


@pytest.mark.parametrize("mode", ["fp32", "q8"])
def test_a_record_is_attributed_by_its_own_program(mode):
    """`of` compiles the record's program from its configuration and mix
    (here the smoke-size one on its XLA path), names two calls' events by
    its schedule, and keeps what it made for the next reader."""
    cfg, mix = smoke.resnet(), smoke.cnn_mix(f"{mode}-b256")
    rec = {"kind": "cnn", "mode": mode, "config": cfg, "mix": mix,
           "batch": mix["batch"]}
    text = scopes.program_text(rec)
    order = scopes.schedule(text, mix["pool"])
    evs = [(trace_reduce.op_name(n), 1000 * k, 1000)
           for k, n in enumerate(order * 2)]
    rec["trace"] = {"session": evs, "batches": 2 * mix["pool"]}
    a = scopes.of(rec)
    assert a["unmapped_n"] == 0
    assert all(r["n"] == r["want"] for r in a["rows"])
    assert scopes.of(rec) is a
    # each event lasts 1 us: a reader's ms a forward counts its scopes'
    # instructions in a call, over the forwards of a call
    prog = scopes.parse(text, mix["pool"])

    def per_forward(pick):
        return sum(pick(prog[n]["scope"]) for n in order) * 1e-3 / \
            mix["pool"]

    bn = per_forward(lambda s: any(p.startswith("bn") or p in (
        "relu", "residual") for p in scopes.parts(s)))
    assert bn > 0
    assert run.read_metric("bn_act_ms.cnn", rec) == pytest.approx(bn)
    q = run.read_metric("quantize_ms.q8", rec)
    if mode == "q8":
        assert q == pytest.approx(per_forward(lambda s: bool(
            {"quantize_act", "quantize_weight"} & set(scopes.parts(s)))))
    else:
        assert q is None
    # the XLA path calls no conv kernel, so nothing is glue around one
    assert run.read_metric("conv_glue_ms.cnn", rec) is None
