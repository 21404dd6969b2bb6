"""Device time of the decode step split by the program's named scopes.

The program names the regions of its fused decode step with
``jax.named_scope`` (``attention``, ``moe_router``, ``moe_experts``,
``lora_hook``, ``lm_head``); a compiled instruction carries the scope
in its metadata's ``op_name`` (``jit(_fused_paged_fn)/lora_hook/...``),
and a fusion takes its root's. On the TPU an ``XLA Ops`` event is named
by its instruction and carries no ``op_name``, but the profile keeps
every program it ran in its ``/host:metadata`` plane, one ``Hlo Proto``
per program, under the same name as the program's ``XLA Modules``
events (``jit__fused_paged_fn(<fingerprint>)``). So each operation of a
decode-step run is joined to its scope by (program, instruction name),
exactly, whatever the bucket.

An instruction the compiler made without metadata (a copy, a DMA, the
pieces of an expanded gather) inherits the op_name of its first user
that has one. The split is reported both ways: each scope's time from
its instructions' own metadata, and the time it inherited.

``jax.profiler.ProfileData`` does not expose that plane's stats, so the
file is read here at the protobuf wire level: only the fields on the
path to ``op_name`` are decoded (field numbers from xla's
``xplane.proto`` and ``hlo.proto``).

Coupling to mend in a later change of the harness: ``LayerContext``
does not carry the path of the profile its ``trace`` was loaded from,
so ``split_of`` imports the entry script (``run``) for its ``OUT_DIR``
and globs the newest profile there again.
"""
from __future__ import annotations

import sys
from typing import Dict, Iterator, List, Optional, Tuple

import trace_reduce
from layerctx import DECODE_MODULE

# innermost first: an operation belongs to the innermost of these
# scopes that its op_name path holds
SCOPES = ("lora_hook", "moe_experts", "moe_router", "attention", "lm_head")
UNSCOPED = "unscoped"


# --------------------------- protobuf wire --------------------------- #
def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for varint and
    fixed fields, a memoryview for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 1:
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wire == 5:
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} not handled")
        yield field, v


def _sub(buf, field: int) -> List:
    return [v for f, v in _fields(buf) if f == field]


def _str(buf, field: int) -> str:
    vs = _sub(buf, field)
    return bytes(vs[-1]).decode() if vs else ""


# XSpace.planes 1; XPlane.name 2, .event_metadata 4 (map: key 1, value
# 2), .stat_metadata 5; XEventMetadata.name 2, .stats 5; XStat
# .metadata_id 1, .bytes_value 6; XStatMetadata.name 2.
# HloProto.hlo_module 1; HloModuleProto.computations 3;
# HloComputationProto.instructions 2; HloInstructionProto.name 1,
# .metadata 7, .id 35, .operand_ids 36; OpMetadata.op_name 2.
def _packed(v) -> List[int]:
    if isinstance(v, int):
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


def _hlo_op_names(hlo: memoryview, inherit: bool = True) -> Dict[str, str]:
    """{instruction name: op_name} of one program. With ``inherit``, an
    instruction the compiler made without metadata (a copy, a DMA, the
    pieces of an expanded gather) takes the op_name of the first of its
    users that has one: its time is charged to the computation that
    consumes it."""
    out: Dict[str, str] = {}
    for module in _sub(hlo, 1):
        for comp in _sub(module, 3):
            name_of: Dict[int, str] = {}
            users: Dict[int, List[int]] = {}
            for ins in _sub(comp, 2):
                name, op_name, iid, operands = "", "", 0, []
                for f, v in _fields(ins):
                    if f == 1:
                        name = bytes(v).decode()
                    elif f == 7:
                        op_name = _str(v, 2)
                    elif f == 35:
                        iid = v
                    elif f == 36:
                        operands += _packed(v)
                name_of[iid] = name
                for o in operands:
                    users.setdefault(o, []).append(iid)
                if op_name:
                    out[name] = op_name
            if not inherit:
                continue
            for iid, name in name_of.items():
                if name not in out:
                    inherited = _from_users(iid, name_of, users, out, set())
                    if inherited:
                        out[name] = inherited
    return out


def _from_users(iid, name_of, users, out, seen) -> str:
    for u in users.get(iid, ()):
        if u in seen:
            continue
        seen.add(u)
        got = out.get(name_of.get(u, "")) or \
            _from_users(u, name_of, users, out, seen)
        if got:
            return got
    return ""


def program_op_names(path: str, inherit: bool = True
                     ) -> Dict[str, Dict[str, str]]:
    """{program name: {instruction name: op_name}} for every program
    the profile holds; ``inherit`` as in ``_hlo_op_names``."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for plane in _sub(space, 1):
        if _str(plane, 2) != "/host:metadata":
            continue
        hlo_ids = {_sub(v, 1)[0] if _sub(v, 1) else 0
                   for entry in _sub(plane, 5) for v in _sub(entry, 2)
                   if _str(v, 2) == "Hlo Proto"}
        for entry in _sub(plane, 4):
            for meta in _sub(entry, 2):
                name = _str(meta, 2)
                for stat in _sub(meta, 5):
                    sid = _sub(stat, 1)
                    blob = _sub(stat, 6)
                    if blob and (sid[0] if sid else 0) in hlo_ids:
                        out[name] = _hlo_op_names(blob[-1], inherit)
    return out


# ------------------------------ the split ----------------------------- #
def scope_of(op_name: Optional[str]) -> str:
    """The innermost of ``SCOPES`` on an op_name path."""
    if not op_name:
        return UNSCOPED
    parts = op_name.split("/")
    for part in reversed(parts):
        if part in SCOPES:
            return part
    return UNSCOPED


def decode_split(trace: trace_reduce.Trace,
                 op_names: Dict[str, Dict[str, str]]
                 ) -> Optional[Dict[str, float]]:
    """Mean device seconds per decode-step run in each scope (and
    ``unscoped``), averaged over the chips; None when the profile has
    no decode-step run or none of its operations is in a scope (a
    program without the named scopes)."""
    per_chip = []
    for lines in trace.devices.values():
        runs = sorted(trace_reduce.matching(
            lines.get(trace_reduce.MODULES_LINE, []), DECODE_MODULE),
            key=lambda e: e.start)
        if not runs:
            continue
        ops = sorted(lines.get(trace_reduce.OPS_LINE, []),
                     key=lambda e: e.start)
        tot: Dict[str, float] = {}
        j = 0
        for run in runs:
            names = op_names.get(run.name, {})
            while j < len(ops) and ops[j].start < run.start:
                j += 1
            while j < len(ops) and ops[j].start < run.end:
                s = scope_of(names.get(ops[j].name))
                tot[s] = tot.get(s, 0.0) + ops[j].dur
                j += 1
        per_chip.append({k: v / len(runs) for k, v in tot.items()})
    if not per_chip or not any(set(c) - {UNSCOPED} for c in per_chip):
        return None
    keys = set().union(*per_chip)
    return {k: sum(c.get(k, 0.0) for c in per_chip) / len(per_chip)
            for k in keys}


_SPLITS: Dict[str, Optional[Dict[str, float]]] = {}


def split_of(ctx) -> Optional[Dict[str, float]]:
    """The decode-step split of the traced run behind ``ctx`` (its
    profile, where run.py leaves it), read once per profile and printed
    on standard error beside the decode step's device time."""
    if ctx.trace is None:
        return None
    import run
    path = trace_reduce.find_xplane(str(run.OUT_DIR / "trace"))
    if path is None:
        return None
    if path not in _SPLITS:
        split = decode_split(ctx.trace, program_op_names(path))
        _SPLITS[path] = split
        if split is not None:
            own = decode_split(ctx.trace, program_op_names(path, False))
            print(report(split, own or {}, ctx), file=sys.stderr,
                  flush=True)
    return _SPLITS[path]


def report(split: Dict[str, float], own: Dict[str, float], ctx) -> str:
    """One line: each scope's device time a decode-step run and its
    share, against the runs' own mean time, with the part of it from the
    instructions' own metadata and the part inherited from a user."""
    runs = [evs for evs in ctx.module_events(DECODE_MODULE) if evs]
    step = sum(sum(e.dur for e in evs) / len(evs) for evs in runs) \
        / max(len(runs), 1)
    parts = []
    for k, v in sorted(split.items(), key=lambda kv: -kv[1]):
        part = f"{k} {1e3 * v:.3f} ms ({100 * v / step:.2f} %"
        if k != UNSCOPED:
            mine = own.get(k, 0.0)
            part += (f"; own {1e3 * mine:.3f}, inherited "
                     f"{1e3 * (v - mine):.3f}")
        parts.append(part + ")")
    return (f"decode step device time by scope, of {1e3 * step:.3f} ms a "
            f"run ({1e3 * sum(split.values()):.3f} ms in its operations; "
            f"unscoped before inheritance "
            f"{1e3 * own.get(UNSCOPED, 0.0):.3f} ms): {', '.join(parts)}")
