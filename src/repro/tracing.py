"""Host spans of the serving loop and the trainer, on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation``: about a microsecond when no
trace is running, and while one is running it lands in the same
``.xplane.pb`` as the device operations, so a reader can lay the host's
work beside the device's. Keyword arguments (request id, slot, task, step)
travel as TraceMe metadata; ``jax.profiler.ProfileData`` gives such an event
its bare name and the arguments as its stats.

``SPANS`` names every span the program opens. Nesting:

    engine.iteration        one turn of the paged host loop
      engine.sweep          cancels and expired deadlines
      engine.admit          one admitted request (rid, slot, task)
        engine.adapter_fault  scatter of a cold task's slice
        engine.cow          copy-on-write of a shared block
      engine.dispatch       the jitted decode-loop call(s)
      engine.wait           first blocking read of the loop's result
      engine.harvest        finished slots back to the pools
    trainer.step            one step (a StepTraceAnnotation, step_num)
      trainer.batch         next batch, host-to-device put included
      trainer.dispatch      the jitted step call
      trainer.wait          the metrics' read, which syncs with the device
      trainer.dmrg          a rank-adaptive sweep
      trainer.checkpoint    a save

Inside the jitted graphs, ``jax.named_scope`` names the phases (``forward``,
``gradient``, ``optimizer``, ``compression``; in the decode loop ``model``,
``sample``, ``bookkeeping``); they change HLO metadata only. Inside the
layers, ``MODEL_SCOPES`` name the latent attention (``mla``) and the parts
of the dropless expert layer (``moe.router``, ``moe.experts``,
``moe.shared``); that layer's step metrics carry the counters
``moe_pairs_here`` (pairs routed to held experts, summed over layers) and
``moe_max_expert_rows`` (the most rows one held expert took in a layer).
To see device time by scope in a trace, or under each of some named
scopes wherever they sit:

    python -m repro.tracing <file.xplane.pb> [--scopes mla,moe.experts]
"""
from __future__ import annotations

import collections
import re
import sys

import jax

SPANS = (
    "engine.iteration", "engine.sweep", "engine.admit",
    "engine.adapter_fault", "engine.cow", "engine.dispatch", "engine.wait",
    "engine.harvest",
    "trainer.step", "trainer.batch", "trainer.dispatch", "trainer.wait",
    "trainer.dmrg", "trainer.checkpoint",
)

#: named scopes inside the model's layers (models/attention.py,
#: models/moe.py)
MODEL_SCOPES = ("mla", "moe.router", "moe.experts", "moe.shared")


def span(name: str, **args):
    """A host span named ``name`` (one of ``SPANS``) carrying ``args``."""
    return jax.profiler.TraceAnnotation(name, **args)


def step_span(name: str, step_num: int):
    """A training-step span: the profiler groups device work by it."""
    return jax.profiler.StepTraceAnnotation(name, step_num=step_num)


# -- device time by named scope ---------------------------------------------
# The op name of a device operation (its HLO ``op_name``) is a stat of the
# event's metadata, which ``ProfileData`` does not expose; this reads the
# few fields it needs straight from the XSpace protobuf's wire format.

def _varint(buf, pos):
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _fields(buf):
    """(field number, value) of one message: ints for varints, memoryviews
    for length-delimited fields; fixed-width fields are skipped."""
    pos = 0
    while pos < len(buf):
        key, pos = _varint(buf, pos)
        kind = key & 7
        if kind == 0:
            val, pos = _varint(buf, pos)
        elif kind == 2:
            n, pos = _varint(buf, pos)
            val, pos = buf[pos:pos + n], pos + n
        elif kind in (1, 5):
            pos += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"wire type {kind} at byte {pos}")
        yield key >> 3, val


def _map_entries(raw_entries):
    """{key: value message} of a protobuf map<int64, message> field."""
    out = {}
    for raw in raw_entries:
        f = dict(_fields(raw))
        out[f.get(1, 0)] = f.get(2, memoryview(b""))
    return out


def _grouped(buf):
    """{field number: [values]} of one message."""
    out = collections.defaultdict(list)
    for num, val in _fields(buf):
        out[num].append(val)
    return out


def _text(vals) -> str:
    return bytes(vals[0]).decode() if vals else ""


def _device_ops(fields):
    """[(op_name, start_ps, end_ps)] of a device plane's "XLA Ops" line
    (``fields``: the plane message, grouped)."""
    stat_names = {k: bytes(dict(_fields(v)).get(2, b"")).decode()
                  for k, v in _map_entries(fields[5]).items()}
    op_name = {}
    for mid, meta in _map_entries(fields[4]).items():
        for num, st in _fields(meta):
            if num != 5:
                continue
            st = dict(_fields(st))
            if stat_names.get(st.get(1)) != "tf_op":
                continue
            op_name[mid] = (bytes(st[5]).decode() if 5 in st
                            else stat_names.get(st.get(7), ""))
    ops = []
    for line in fields[3]:
        lf = _grouped(line)
        if _text(lf[2]) != "XLA Ops":
            continue
        t0 = (lf[3][0] if lf[3] else 0) * 1000
        for ev in lf[4]:
            e = dict(_fields(ev))
            s = t0 + e.get(2, 0)
            ops.append((op_name.get(e.get(1), ""), s, s + e.get(3, 0)))
    return ops


def _leaves(ops):
    """Drop operations that enclose others (a while loop holds its body)."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt[1] >= o[2]]


_WRAPPERS = re.compile(r"^(p?jit\(.*\)|while|body|cond)$")


def scope_of(op_name: str, depth: int = 2) -> str:
    """The first ``depth`` named scopes of an op name, jit and loop
    wrappers dropped:
    "jit(step_fn)/gradient/transpose(jvp(forward))/while/body/dot_general"
    -> "gradient/transpose(jvp(forward))"; "jit(f)/while/body/model/dot"
    -> "model"."""
    parts = [p for p in op_name.split("/")[:-1]
             if p and not _WRAPPERS.match(p)]
    return "/".join(parts[:depth]) or "(none)"


def scope_times(path: str, depth: int = 2) -> dict:
    """Device seconds of the trace at ``path`` by top-level named scope,
    leaf operations only, averaged over the device planes."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    acc, planes = collections.Counter(), 0
    for plane in _grouped(space)[1]:
        fields = _grouped(plane)
        if not re.match(r"^/device:TPU:\d+$", _text(fields[2])):
            continue
        planes += 1
        for op, s, e in _leaves(_device_ops(fields)):
            acc[scope_of(op, depth)] += (e - s) * 1e-12
    return {k: v / max(planes, 1) for k, v in acc.most_common()}


def named_scope_times(path: str, names) -> dict:
    """Device seconds of the trace at ``path`` under each named scope of
    ``names``, wherever it sits in an op's name (forward, backward or
    remat's recompute), leaf operations only, averaged over the device
    planes; "(other)" holds the rest."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    acc, planes = collections.Counter({n: 0.0 for n in names}), 0
    for plane in _grouped(space)[1]:
        fields = _grouped(plane)
        if not re.match(r"^/device:TPU:\d+$", _text(fields[2])):
            continue
        planes += 1
        for op, s, e in _leaves(_device_ops(fields)):
            parts = op.split("/")
            hit = next((n for n in names if n in parts), "(other)")
            acc[hit] += (e - s) * 1e-12
    return {k: v / max(planes, 1) for k, v in acc.most_common()}


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) == 3 and argv[1] == "--scopes":
        times = named_scope_times(argv[0], argv[2].split(","))
    elif len(argv) == 1:
        times = scope_times(argv[0])
    else:
        print("usage: python -m repro.tracing <file.xplane.pb> "
              "[--scopes a,b,...]", file=sys.stderr)
        return 2
    total = sum(times.values()) or 1.0
    for k, v in times.items():
        print(f"{v:12.6f} s {100 * v / total:6.2f}%  {k}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
