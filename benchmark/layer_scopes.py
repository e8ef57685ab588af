"""Device seconds by (layer, pass), from a profiler trace (``.xplane.pb``)
and the optimized HLO the trace already carries.

The TPU's ``XLA Ops`` line names each operation after its HLO instruction
(``%fusion.709 = ...``), not after the scope it was staged in.  But the
trace's ``/host:metadata`` plane holds each program's optimized HLO as an
``Hlo Proto`` stat, under an event-metadata name ``<module>(<program id>)``,
and every instruction there keeps the path jax staged it under in its
``metadata.op_name`` (``jit(step)/transpose(jvp(cnet))/conv_general_dilated``).
Each operation event carries ``program_id`` and ``hlo_op`` stats, so it
joins to its instruction, and through it to its layer and its pass:

- the layer is the first component of the path, with jax's transform
  wrappers (``jvp(...)``, ``transpose(...)``) taken off, that is in
  ``LAYERS``: the benchmark's own copy of the program's scope names, so
  that a change in the program cannot move this yardstick;
- the pass is ``recompute`` where the path holds ``rematted_computation``,
  else ``backward`` where it holds ``transpose(``, else ``forward``.

A fusion counts where its ROOT was staged: the fused computation's root
instruction's path decides, whatever the rest of the fusion holds.  An
event is charged only its self time (``trace_reduce.self_times``: a
``while`` that holds its body is charged only its own time), and the
seconds are the mean over the device planes, as ``reduce_profile`` takes
them.  Time that joins to no instruction, or to one staged under no listed
layer, is kept under the layer ``None``, so that coverage can be read.

The file is read with ``google.protobuf``'s runtime descriptors and the few
message types needed (field numbers of ``tsl/profiler/protobuf/xplane.proto``
and ``xla/service/hlo.proto``): no tensorflow import.  A file is parsed once
per process, whatever number of readers ask for it.
"""

from __future__ import annotations

import functools
import glob
import os
import re
from typing import Dict, Optional, Tuple

from benchmark import harness, trace_reduce

# The program's layer scopes (``raft_stereo_tpu/profiling.LAYERS``), copied.
LAYERS = ("prep", "fnet", "cnet", "features", "gev", "corr_pyramid",
          "gru_iter", "upsample", "confidence", "loss", "optimizer")

_FIELDS = {   # message: [(field, number, type, repeated, message type)]
    "XSpace": [("planes", 1, "message", True, "XPlane")],
    "XPlane": [("id", 1, "int64", False, None),
               ("name", 2, "string", False, None),
               ("lines", 3, "message", True, "XLine"),
               ("event_metadata", 4, "message", True, "EventMetadataEntry"),
               ("stat_metadata", 5, "message", True, "StatMetadataEntry")],
    "EventMetadataEntry": [("key", 1, "int64", False, None),
                           ("value", 2, "message", False, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, "int64", False, None),
                          ("value", 2, "message", False, "XStatMetadata")],
    "XLine": [("name", 2, "string", False, None),
              ("timestamp_ns", 3, "int64", False, None),
              ("events", 4, "message", True, "XEvent")],
    "XEvent": [("metadata_id", 1, "int64", False, None),
               ("offset_ps", 2, "int64", False, None),
               ("duration_ps", 3, "int64", False, None),
               ("stats", 4, "message", True, "XStat")],
    "XStat": [("metadata_id", 1, "int64", False, None),
              ("double_value", 2, "double", False, None),
              ("uint64_value", 3, "uint64", False, None),
              ("int64_value", 4, "int64", False, None),
              ("str_value", 5, "string", False, None),
              ("bytes_value", 6, "bytes", False, None),
              ("ref_value", 7, "uint64", False, None)],
    "XEventMetadata": [("id", 1, "int64", False, None),
                       ("name", 2, "string", False, None),
                       ("stats", 5, "message", True, "XStat")],
    "XStatMetadata": [("id", 1, "int64", False, None),
                      ("name", 2, "string", False, None)],
    "HloProto": [("hlo_module", 1, "message", False, "HloModuleProto")],
    "HloModuleProto": [("name", 1, "string", False, None),
                       ("computations", 3, "message", True,
                        "HloComputationProto"),
                       ("entry_computation_id", 6, "int64", False, None)],
    "HloComputationProto": [("name", 1, "string", False, None),
                            ("instructions", 2, "message", True,
                             "HloInstructionProto"),
                            ("id", 5, "int64", False, None),
                            ("root_id", 6, "int64", False, None)],
    "HloInstructionProto": [("name", 1, "string", False, None),
                            ("opcode", 2, "string", False, None),
                            ("metadata", 7, "message", False, "OpMetadata"),
                            ("id", 35, "int64", False, None),
                            ("called_computation_ids", 38, "int64", True,
                             None)],
    "OpMetadata": [("op_type", 1, "string", False, None),
                   ("op_name", 2, "string", False, None)],
}
_PACKAGE = "benchmark_layer_scopes"
_CLASSES: Dict[str, type] = {}


def _classes() -> Dict[str, type]:
    if _CLASSES:
        return _CLASSES
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    fdp = descriptor_pb2.FileDescriptorProto(
        name=_PACKAGE + ".proto", package=_PACKAGE, syntax="proto3")
    F = descriptor_pb2.FieldDescriptorProto
    for msg, fields in _FIELDS.items():
        m = fdp.message_type.add(name=msg)
        for name, number, kind, repeated, sub in fields:
            f = m.field.add(name=name, number=number,
                            label=F.LABEL_REPEATED if repeated
                            else F.LABEL_OPTIONAL,
                            type=getattr(F, "TYPE_" + kind.upper()))
            if sub:
                f.type_name = f".{_PACKAGE}.{sub}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    for msg in _FIELDS:
        _CLASSES[msg] = message_factory.GetMessageClass(
            pool.FindMessageTypeByName(f"{_PACKAGE}.{msg}"))
    return _CLASSES


def layer_of(op_name: str) -> Optional[str]:
    """The first component of an ``op_name`` path, transform wrappers
    taken off, that is a listed layer; None where there is none."""
    for part in op_name.split("/"):
        while True:
            m = re.fullmatch(r"[\w\-]+\((.*)\)", part)
            if not m:
                break
            part = m.group(1)
        if part in LAYERS:
            return part
    return None


def pass_of(op_name: str) -> Optional[str]:
    """``recompute``, ``backward``, or None where the path carries neither
    mark (the forward, unless the path sits in a loop's body)."""
    if "rematted_computation" in op_name:
        return "recompute"
    if "transpose(" in op_name:
        return "backward"
    return None


def placements(module) -> Dict[str, Tuple[Optional[str], str]]:
    """Instruction name -> (layer, pass) for every instruction of one
    program (an ``HloModuleProto``).  A fusion is placed by its fused
    root's path.  A computation that a ``while``, ``call`` or
    ``conditional`` runs (a scan's body) was traced with a name stack of
    its own, so its paths start afresh (``RAFTStereo.body_train/...``):
    where such a path names no layer, or no pass, the instruction takes
    the one of the operation that runs its computation."""
    comps = {c.id: c for c in module.computations}
    by_id = {ins.id: ins for c in comps.values() for ins in c.instructions}
    out: Dict[str, Tuple[Optional[str], str]] = {}
    seen = set()

    def visit(comp_id, outer):
        if comp_id in seen or comp_id not in comps:
            return
        seen.add(comp_id)
        for ins in comps[comp_id].instructions:
            path = ins.metadata.op_name
            if ins.opcode == "fusion" and ins.called_computation_ids:
                fused = comps.get(ins.called_computation_ids[0])
                root = fused and by_id.get(fused.root_id)
                path = (root is not None and root.metadata.op_name) or path
            outer_layer, outer_pass = outer or (None, "forward")
            here = (layer_of(path) or outer_layer,
                    pass_of(path) or outer_pass)
            out[ins.name] = here
            for called in ins.called_computation_ids:
                visit(called, here)

    visit(module.entry_computation_id, None)
    for comp_id in comps:
        visit(comp_id, None)
    return out


def _stats(stats, stat_names: Dict[int, str]) -> dict:
    out = {}
    for s in stats:
        name = stat_names.get(s.metadata_id)
        if name is None:
            continue
        if s.ref_value:
            out[name] = stat_names.get(s.ref_value, "")
        elif s.str_value:
            out[name] = s.str_value
        elif s.bytes_value:
            out[name] = s.bytes_value
        elif s.int64_value:
            out[name] = s.int64_value
        elif s.uint64_value:
            out[name] = s.uint64_value
        else:
            out.setdefault(name, s.double_value)
    return out


def _instruction(event_name: str, stats: dict) -> str:
    """The instruction an event is: its ``hlo_op`` stat, else the head of
    its name (``%fusion.709 = bf16[..] fusion(..)`` -> ``fusion.709``)."""
    if stats.get("hlo_op"):
        return str(stats["hlo_op"])
    m = re.match(r"\s*%?([\w.\-]+)", event_name)
    return m.group(1) if m else event_name


def reduce_space(data: bytes, host_stand_in: bool = False) -> dict:
    """``{"seconds": {(layer, pass): s}, "busy_s", "joined_s",
    "programs", "matched_by_names", "unscoped"}`` of one serialized
    XSpace.  ``programs`` counts the ``Hlo Proto`` stats found; with none,
    nothing joins and every second is ``(None, None)``'s.  An executable
    read from jax's persistent cache runs under the program id it was
    compiled with, while the metadata plane files its HLO under a new one:
    such a program is matched to the HLO of its module name (any, where the
    event names none) that holds the most of its instructions
    (``matched_by_names`` counts them).  ``unscoped``: the ten operations
    with most seconds under no listed layer, ``[instruction, s]``.
    ``host_stand_in`` as in ``trace_reduce.reduce_profile``: the host
    plane's XLA-client lines in place of device planes, for the
    benchmark's own CPU rehearsal."""
    space = _classes()["XSpace"]()
    space.ParseFromString(data)
    hlo: Dict[int, bytes] = {}
    modules: Dict[int, str] = {}
    for plane in space.planes:
        if plane.name != "/host:metadata":
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        for e in plane.event_metadata:
            proto = _stats(e.value.stats, stat_names).get("Hlo Proto")
            m = re.fullmatch(r"(.*)\((\d+)\)", e.value.name)
            if proto and m:
                hlo[int(m.group(2))] = proto
                modules[int(m.group(2))] = m.group(1)
    planes = [(p, [ln for ln in p.lines
                   if ln.name in trace_reduce.OPS_LINES])
              for p in space.planes if p.name.startswith("/device:")]
    planes = [(p, lines) for p, lines in planes if lines]
    if not planes and host_stand_in:
        planes = [(p, [ln for ln in p.lines
                       if "XLAPjRt" in ln.name or "XLAEigen" in ln.name])
                  for p in space.planes if p.name.startswith("/host:CPU")]
    if not planes:
        raise harness.BenchError(
            "the trace has no device plane with an operations line")
    places: Dict[int, Dict[str, Tuple[Optional[str], str]]] = {}

    def placed(program: int) -> Dict[str, Tuple[Optional[str], str]]:
        if program not in places:
            proto = _classes()["HloProto"]()
            proto.ParseFromString(hlo[program])
            places[program] = placements(proto.hlo_module)
        return places[program]

    per_plane, names_of, module_of = [], {}, {}
    for plane, lines in planes:
        metas = {e.key: e.value for e in plane.event_metadata}
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        known: Dict[int, Optional[dict]] = {}     # metadata id -> its stats
        events = []
        for ln in lines:
            for ev in ln.events:
                if ev.metadata_id not in known:
                    meta = metas.get(ev.metadata_id)
                    known[ev.metadata_id] = None if (
                        meta is None or "::" in meta.name) else dict(
                        _stats(meta.stats, stat_names), name=meta.name)
                stats = known[ev.metadata_id]
                if stats is None or ev.duration_ps <= 0:
                    continue
                if "program_id" not in stats and len(ev.stats):
                    stats = dict(stats, **_stats(ev.stats, stat_names))
                program = stats.get("program_id")
                program = None if program is None else int(program)
                instr = _instruction(stats["name"], stats)
                if program is not None and program not in hlo:
                    names_of.setdefault(program, set()).add(instr)
                    if stats.get("hlo_module"):
                        module_of[program] = str(stats["hlo_module"])
                start = ln.timestamp_ns * 1000 + ev.offset_ps
                events.append((start, start + ev.duration_ps, program,
                               instr))
        per_plane.append(events)
    alias = {}
    for program, names in names_of.items():
        pool = [q for q in hlo if modules[q] == module_of.get(program)] \
            or list(hlo)
        best = max(pool, key=lambda q: len(names & placed(q).keys()),
                   default=None)
        if best is not None and names & placed(best).keys():
            alias[program] = best
    seconds: Dict[Tuple[Optional[str], Optional[str]], float] = {}
    busy = joined = 0.0
    unscoped: Dict[str, float] = {}
    for events in per_plane:
        keyed = [(a, b, f"{alias.get(program, program)}\t{instr}", "")
                 for a, b, program, instr in events]
        # self_times counts its units as ns; these are ps
        for key, own in trace_reduce.self_times(keyed).items():
            own *= 1e-3 / len(planes)
            busy += own
            program, instr = key.split("\t")
            where: Tuple[Optional[str], Optional[str]] = (None, None)
            if program != "None" and int(program) in hlo \
                    and instr in placed(int(program)):
                where = placed(int(program))[instr]
                joined += own
            if where[0] is None:
                unscoped[instr] = unscoped.get(instr, 0.0) + own
            seconds[where] = seconds.get(where, 0.0) + own
    top = sorted(unscoped.items(), key=lambda kv: -kv[1])[:10]
    return {"seconds": seconds, "busy_s": busy, "joined_s": joined,
            "programs": len(hlo), "matched_by_names": len(alias),
            "unscoped": [[op, s] for op, s in top]}


def reduce_file(path: str, host_stand_in: bool = False) -> dict:
    """``reduce_space`` of one file, parsed once per process however many
    readers ask (the file's path, time and size are the key)."""
    st = os.stat(path)
    return _reduce_once(os.path.abspath(path), st.st_mtime_ns, st.st_size,
                        host_stand_in)


@functools.lru_cache(maxsize=4)
def _reduce_once(path: str, _mtime_ns: int, _size: int,
                 host_stand_in: bool) -> dict:
    with open(path, "rb") as f:
        return reduce_space(f.read(), host_stand_in)


def for_run(observed: dict) -> Optional[dict]:
    """The layers of the run ``observed`` describes: the newest trace file
    under its cell's work directory (``<WORK_ROOT>/<cell>/trace``, where
    every entry that traces puts it and the next run of the cell empties
    it), or None where the run reduced no trace of its own (a reduction
    names its device planes), the file holds no ``Hlo Proto``, or nothing
    joined."""
    tr = observed.get("trace")
    if not tr or "device_planes" not in tr or tr.get("busy_s", 0) <= 0:
        return None
    files = glob.glob(os.path.join(
        harness.WORK_ROOT, observed["cell"]["name"], "trace", "**",
        "*.xplane.pb"), recursive=True)
    if not files:
        return None
    out = reduce_file(max(files, key=os.path.getmtime),
                      host_stand_in=bool(tr.get("stand_in_host_plane")))
    if out["programs"] == 0 or out["joined_s"] <= 0 or out["busy_s"] <= 0:
        return None
    return out


def share_pct(observed: dict, layers=None, passes=None) -> Optional[float]:
    """Device seconds of the listed layers (any, where None) in the listed
    passes (any, where None) over all the operations' seconds, in %; None
    where there is nothing to read or nothing of them ran: a share is
    never reported as 0 in the place of a layer that has no scope."""
    run = for_run(observed)
    if run is None:
        return None
    seconds = sum(s for (layer, kind), s in run["seconds"].items()
                  if (layers is None or layer in layers)
                  and (passes is None or kind in passes))
    if seconds <= 0:
        return None
    return 100.0 * seconds / run["busy_s"]
