"""Attribution of device time to the program's named scopes.

The program runs its parts under `jax.named_scope` (the ResNet-18 forward:
`stem`, `s{stage}b{block}`, `head`, and inside them the layer, `bn*`,
`relu`, `residual`, `quantize_act`, `quantize_weight`; the fused conv's
wrapper: `phases`, `weights`, `crop`). A scope is op-name metadata of the
compiled program and nothing else. A traced run's record keeps each device
event by the op name the trace gives it (`_conv_jit`, `pad_bitcast_fusion`:
the HLO instruction's name without its number), so an event reaches its
scope only through the compiled program's text:

* `parse` maps every instruction of the program's non-fused computations
  (the entry, a loop's body and condition) to a scope path such as
  `s1b0/conv1/phases`: the op name without the components JAX writes
  itself (`jit(run)`, `while`, `body`, `closed_call`, ...) and without the
  primitive at its end. A fusion is charged to the scope of its fused
  computation's root; `spans` lists every scope its instructions come
  from where there are several. An instruction the compiler made without
  an op name (a layout copy, an async copy's start and done, the
  concatenation of a weight's slices) takes the scope of its first user
  that has one, else of its first operand.
* `schedule` lists the instructions with a device event in the order one
  call of the program runs them (a scheduled module's text lists each
  computation's instructions in that order, and a loop repeats its
  condition and body), and `align` names each event of a trace by the
  instruction at its place in that order.
* `attribute` charges each event, by its instruction's name, to that
  instruction's scope, and sums time and count per (scope, op) row beside
  the count the program gives for the calls traced.

`of` does all of this once for a traced CNN run, from the record the
readers are given: it compiles the run's program again from the record's
configuration and mix (the persistent compile cache serves it) and logs
the scopes with most time to stderr. A reader sums the rows it names
(`ms_per_forward`); it reports nothing where no event matched or where
the events counted differ from what the program gives.
"""
from __future__ import annotations

import bisect
import importlib.util
import re
import sys
import time
from pathlib import Path
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import trace_reduce

BENCH = Path(__file__).resolve().parent

# name-stack components JAX writes itself for a loop and a closed call; any
# component with a parenthesis (`jit(run)`) is a transformation's
JAX_WORDS = frozenset({"while", "body", "cond", "closed_call"})
# instructions that never have a device event of their own; nor has an
# unfused instruction with a scalar result (a loop counter, a scale): the
# core's scalar unit runs it between the vector ops (seen on TPU v5 lite)
NO_EVENT = frozenset({"parameter", "constant", "get-tuple-element", "tuple",
                      "bitcast"})
_SCALAR = re.compile(r"^[a-z][a-z0-9]*\[\]")

_COMP = re.compile(r"^(ENTRY )?%(\S+) .*\{$")
_INSTR = re.compile(r"^\s+(ROOT )?%(\S+) = (.*)$")
_OPCODE = re.compile(r"(?:^|[\s)}\]])([a-z][a-z0-9-]*)\(")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%([\w.\-]+)")
_BODY = re.compile(r"\bbody=%([\w.\-]+)")
_COND = re.compile(r"\bcondition=%([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")

Entry = Dict[str, object]


def scope_of(op_name: str) -> str:
    """`jit(run)/while/body/closed_call/s1b0/conv1/jit(_conv_jit)/phases/pad`
    -> `s1b0/conv1/phases`. Where the compiler merged ops (`a;b`), the
    first op's path."""
    parts = op_name.split(";")[0].split("/")[:-1]
    return "/".join(p for p in parts
                    if p and p not in JAX_WORDS and "(" not in p)


def _computations(text: str) -> Tuple[Dict[str, List[Tuple]], str]:
    """{computation: [(name, opcode, rest, is_root)]}, and the entry's
    name."""
    comps: Dict[str, List[Tuple]] = {}
    entry, cur = "", None
    for line in text.splitlines():
        if cur is None:
            m = _COMP.match(line)
            if m:
                cur = m.group(2)
                comps[cur] = []
                if m.group(1):
                    entry = cur
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _INSTR.match(line)
        if m:
            rest = m.group(3)
            op = _OPCODE.search(rest)
            comps[cur].append((m.group(2), op.group(1) if op else "",
                               rest, bool(m.group(1))))
    if not entry:
        raise ValueError("no ENTRY computation in the program's text")
    return comps, entry


def _has_event(opcode: str, rest: str) -> bool:
    return opcode not in NO_EVENT and (opcode == "fusion"
                                       or not _SCALAR.match(rest))


def parse(text: str, loop_trips: int) -> Dict[str, Entry]:
    """{instruction: {"scope", "op", "runs", "spans"}} for every
    instruction with a device event in the non-fused computations of
    compiled HLO `text` (`compiled.as_text()`). `runs` is how often one
    call of the program runs it: once in the entry, `loop_trips` times in
    a loop's body (the compiled text does not say) and once more in its
    condition. `op` is the name without its number, as the trace's op
    names have it."""
    comps, entry = _computations(text)
    op_names: Dict[str, str] = {}
    for instrs in comps.values():
        for name, _, rest, _ in instrs:
            m = _OP_NAME.search(rest)
            # an op name that is only an argument's name (`x`) marks the
            # layout copy of an argument: it is charged like no op name
            if m and "/" in m.group(1):
                op_names[name] = m.group(1)

    # how often each non-fused computation runs in one call
    runs = {entry: 1}
    order = [entry]
    for comp in order:
        for _, opcode, rest, _ in comps[comp]:
            body, cond = _BODY.search(rest), _COND.search(rest)
            if opcode == "while" and body and cond:
                runs[body.group(1)] = runs[comp] * loop_trips
                runs[cond.group(1)] = runs[comp] * (loop_trips + 1)
                order += [body.group(1), cond.group(1)]

    out: Dict[str, Entry] = {}
    users: Dict[str, List[str]] = {}
    operands: Dict[str, List[str]] = {}
    for comp in order:
        for name, opcode, rest, _ in comps[comp]:
            operands[name] = _OPERAND.findall(rest.split("),")[0])
            for a in operands[name]:
                users.setdefault(a, []).append(name)
            if not _has_event(opcode, rest):
                continue
            e: Entry = {"op": trace_reduce.op_name(name), "runs": runs[comp],
                        "spans": []}
            own = name
            if opcode == "fusion":
                fused = comps[_CALLS.search(rest).group(1)]
                root = next(n for n, _, _, is_root in fused if is_root)
                # a multi-output fusion's root tuple has no op name
                if root in op_names:
                    own = root
                # constants are shared across scopes by the compiler, so
                # their op names say nothing
                spans = {scope_of(op_names[n]) for n, op, _, _ in fused
                         if n in op_names and op != "constant"} - {""}
                if len(spans) > 1:
                    e["spans"] = sorted(spans)
            if own in op_names:
                e["scope"] = scope_of(op_names[own])
            out[name] = e

    def inherit(name: str, seen: set) -> Optional[str]:
        if "scope" in out.get(name, {}):
            return out[name]["scope"]
        if name in op_names:
            return scope_of(op_names[name])
        seen.add(name)
        for nxt in users.get(name, []) + operands.get(name, []):
            if nxt not in seen:
                s = inherit(nxt, seen)
                if s is not None:
                    return s
        return None

    for name, e in out.items():
        if "scope" not in e:
            e["scope"] = inherit(name, set()) or ""
    return out


def schedule(text: str, loop_trips: int) -> List[str]:
    """The instructions of compiled HLO `text` that have a device event
    (those `parse` maps), in the order one call of the program runs them:
    each computation's instructions as the scheduled text lists them, and
    in a loop's place its condition and body in turn (`loop_trips` bodies,
    one more condition). A loop's own event, which spans its body's, is
    left out."""
    comps, entry = _computations(text)

    def runs(comp: str) -> List[str]:
        out: List[str] = []
        for name, opcode, rest, _ in comps[comp]:
            body, cond = _BODY.search(rest), _COND.search(rest)
            if opcode == "while" and body and cond:
                c, b = runs(cond.group(1)), runs(body.group(1))
                out += c + (b + c) * loop_trips
            elif (_has_event(opcode, rest) and trace_reduce.op_name(name)
                  not in trace_reduce.CONTAINERS):
                out.append(name)
        return out

    return runs(entry)


def align(events: Iterable[Tuple[str, int, int]], order: Sequence[str],
          calls: int) -> List[Tuple[str, int, int]]:
    """Device events (op, start_ns, dur_ns), by start, of `calls` calls of
    a program run back to back, each named by the instruction it ran: the
    next one of its op in the calls' order (`schedule`). An event with no
    such instruction ahead is named "" and counts as unmapped; loops' own
    events are left out."""
    want = list(order) * calls
    at: Dict[str, List[int]] = {}
    for k, name in enumerate(want):
        at.setdefault(trace_reduce.op_name(name), []).append(k)
    out, i = [], 0
    for op, s, d in events:
        if op in trace_reduce.CONTAINERS:
            continue
        ks = at.get(op, [])
        j = bisect.bisect_left(ks, i)
        if j == len(ks):
            out.append(("", s, d))
        else:
            out.append((want[ks[j]], s, d))
            i = ks[j] + 1
    return out


def attribute(events: Iterable[Tuple[str, int, int]],
              program: Dict[str, Entry], calls: int) -> Dict:
    """Device events (instruction name, start_ns, dur_ns) of `calls` calls
    of `program` (`parse`), charged to scopes:

    * ``rows``: one per (scope, op): summed ``ns``, events counted ``n``,
      and events the program gives for the calls (``want``);
    * ``unmapped_ns``, ``unmapped_n``: events of no instruction of the
      program (another program's, or one `align` could not place);
    * ``events_ns``: all events' time but the loops' containers;
    * ``busy_ns``: the union of those events' intervals.
    """
    rows: Dict[Tuple[str, str], Dict] = {}
    for name, e in program.items():
        if e["op"] in trace_reduce.CONTAINERS:
            continue
        r = rows.setdefault((e["scope"], e["op"]), {
            "scope": e["scope"], "op": e["op"], "ns": 0, "n": 0, "want": 0})
        r["want"] += e["runs"] * calls
    un_ns = un_n = ev_ns = 0
    spans = []
    for name, s, d in events:
        e = program.get(name)
        if e is None:
            if trace_reduce.op_name(name) in trace_reduce.CONTAINERS:
                continue
            un_ns += d
            un_n += 1
        elif e["op"] in trace_reduce.CONTAINERS:
            continue
        else:
            r = rows[(e["scope"], e["op"])]
            r["ns"] += d
            r["n"] += 1
        ev_ns += d
        spans.append((s, s + d))
    return {"rows": sorted(rows.values(), key=lambda r: -r["ns"]),
            "unmapped_ns": un_ns, "unmapped_n": un_n, "events_ns": ev_ns,
            "busy_ns": trace_reduce.total(trace_reduce.union(spans))}


def parts(scope: str) -> List[str]:
    return scope.split("/") if scope else []


def program_text(rec: Dict) -> str:
    """The compiled text of the program a CNN run timed: the driver's
    `pool_forward` over the record's pool, at its configuration, mode and
    batch, lowered from shapes alone. It is the program the run compiled
    at set-up, so the persistent compile cache serves it."""
    import jax
    import jax.numpy as jnp

    from repro.models.cnn import resnet18

    spec = importlib.util.spec_from_file_location(
        "scopes_cnn_driver", BENCH / "drivers" / "cnn.py")
    cnn = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cnn)
    cfg, mix = rec["config"], rec["mix"]
    # no sharding: the run's arrays are not committed to a device, and one
    # would put sharding annotations into the program
    arg = jax.ShapeDtypeStruct
    params, state = jax.tree_util.tree_map(
        lambda a: arg(a.shape, a.dtype), jax.eval_shape(
            lambda k: resnet18.init(k, num_classes=cfg["num_classes"],
                                    in_ch=cfg["in_ch"], width=cfg["width"]),
            jax.random.PRNGKey(0)))
    hw = cfg["image_hw"]
    xs = arg((mix["pool"], mix["batch"], hw, hw, cfg["in_ch"]), jnp.float32)
    fwd = cnn.pool_forward(cnn.layer_mode(cfg, cfg["modes"][mix["mode"]]),
                           mix["pool"])
    return jax.jit(fwd).lower(params, state, xs).compile().as_text()


def of(rec: Dict) -> Optional[Dict]:
    """The attribution (`attribute`) of a traced CNN run's device events,
    made on the first call and kept in the record's trace under "scopes";
    None for another kind of run or an untraced one. Making it logs the
    scopes with most device time a forward and what it cost."""
    t = rec.get("trace")
    if rec.get("kind") != "cnn" or not t or not t.get("batches"):
        return None
    if "scopes" not in t:
        t0 = time.perf_counter()
        pool = rec["mix"]["pool"]
        text = program_text(rec)
        t1 = time.perf_counter()
        calls = t["batches"] // pool
        t["scopes"] = attribute(align(t["session"], schedule(text, pool),
                                      calls),
                                parse(text, pool), calls)
        _log(t["scopes"], t["batches"])
        _say(f"scope attribution: program {t1 - t0:.3f} s, map and "
                 f"{len(t['session'])} events {time.perf_counter() - t1:.3f}"
                 f" s")
    return t["scopes"]


def _say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _log(a: Dict, forwards: int, k: int = 25) -> None:
    """The `k` scopes with most device time a forward, the unscoped
    remainder, and all of them against the busy time of the ops."""
    table = by_scope(a, forwards)
    _say(f"device time a forward by scope over {forwards} forwards "
             f"(scope: total ms, self ms, events):")
    for s, tot, own, cnt in table[:k] + [r for r in table[k:] if not r[0]]:
        _say(f"  {s or '(unscoped)'}: {tot:.4f}, {own:.4f}, "
                 f"{cnt / forwards:g}")
    _say(f"scopes and unscoped {a['events_ns'] * 1e-6 / forwards:.4f} "
             f"ms, busy {a['busy_ns'] * 1e-6 / forwards:.4f} ms a forward")


def ms_per_forward(rec: Dict, pick: Callable[[Dict], bool]
                   ) -> Optional[float]:
    """Device time per traced forward, in ms, of the rows of a run's
    attribution (`of`) that `pick` takes; None where no event matched, or
    where their events differ in number from what the compiled program
    gives."""
    a = of(rec)
    if not a:
        return None
    picked = [r for r in a["rows"] if pick(r)]
    n = sum(r["n"] for r in picked)
    if n == 0 or n != sum(r["want"] for r in picked):
        return None
    return sum(r["ns"] for r in picked) * 1e-6 / rec["trace"]["batches"]


def by_scope(a: Dict, forwards: int) -> List[Tuple[str, float, float, int]]:
    """(scope, total ms, self ms, events) per forward for every scope and
    each of its enclosing scopes, longest total first; the unscoped
    remainder (no scope, or no instruction of the program) is `""`."""
    total: Dict[str, float] = {}
    own: Dict[str, float] = {}
    count: Dict[str, int] = {}
    for r in a["rows"]:
        ms = r["ns"] * 1e-6 / forwards
        own[r["scope"]] = own.get(r["scope"], 0.0) + ms
        count[r["scope"]] = count.get(r["scope"], 0) + r["n"]
        p = parts(r["scope"])
        for k in range(1, len(p) + 1):
            s = "/".join(p[:k])
            total[s] = total.get(s, 0.0) + ms
    un = a["unmapped_ns"] * 1e-6 / forwards
    own[""] = own.get("", 0.0) + un
    count[""] = count.get("", 0) + a["unmapped_n"]
    total[""] = own[""]
    return sorted(((s, total[s], own.get(s, 0.0), count.get(s, 0))
                   for s in total), key=lambda x: -x[1])


def kernel_scopes(rec: Dict, kernels: Sequence[str]) -> List[str]:
    """The scopes the calls of `kernels` (ops, by name) were charged to in
    a run's attribution (`of`)."""
    a = of(rec) or {"rows": []}
    return sorted({r["scope"] for r in a["rows"]
                   if r["op"] in kernels and r["n"]})
