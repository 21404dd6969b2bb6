"""What a per-layer metric reader is given, and the lookups several of
them share. A reader is ``metrics/<name>.py`` with ``LAYER``, ``UNIT``,
``SOURCE``, ``MOVES`` and ``read(ctx) -> float | None``; ``None`` means
there was nothing to read, and the harness then leaves the metric out.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

from harness import Record, Round
from spec import load_module
import trace_reduce

# XLA module of the jitted decode step (transport/fused.py): the
# module-level program, or the per-transport closure under a mesh. A
# module's event is named "<module>(<id>)".
DECODE_MODULE = r"^(jit__fused_paged_fn|jit_paged)\b"
# the Mosaic paged-attention kernel (kernels/paged.py): its custom call
# takes the name of the jitted wrapper kernels/ops._paged_attention_call,
# "_paged_attention_call.<n>", one per layer in each decode step
PAGED_KERNEL = r"^_paged_attention_call(\.\d+)?$"


@dataclasses.dataclass
class LayerContext:
    record: Record
    dims: Dict
    peak: Dict
    chips: int
    trace: Optional[trace_reduce.Trace] = None
    layout: object = None         # the configuration's layouts/<name>.py

    def traced_rounds(self) -> List[Round]:
        """Decode rounds that ran wholly inside the traced slice."""
        span = self.record.trace_span
        if span is None or span[1] is None:
            return []
        return [r for r in self.record.rounds if r.contexts
                and r.start >= span[0] and r.end <= span[1]]

    def device_lines(self):
        return list(self.trace.devices.values()) if self.trace else []

    def module_events(self, pattern: str):
        """Per device, the runs of the programs matching ``pattern``."""
        return [trace_reduce.matching(lines.get(trace_reduce.MODULES_LINE,
                                                []), pattern)
                for lines in self.device_lines()]

    def op_events(self, pattern: str):
        """Per device, the operations matching ``pattern``."""
        return [trace_reduce.matching(lines.get(trace_reduce.OPS_LINE, []),
                                      pattern)
                for lines in self.device_lines()]


def load_reader(name: str):
    return load_module(f"metrics/{name}.py")


def applies(entry: Dict, cell: str, e2e_names) -> bool:
    """Whether a per-layer metric is read in ``cell``: its ``workloads``
    list names the cell, or it has none and the cell reports the
    end-to-end metric it moves."""
    if "workloads" in entry:
        return cell in entry["workloads"]
    return entry["moves"] in e2e_names
