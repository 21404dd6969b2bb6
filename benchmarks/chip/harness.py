"""Build the system under test from a configuration and a seed, warm up
the shapes the cell's traffic uses, and drive the measured window as an
open loop on the wall clock.

The loop submits each request through ``ServeSystem.submit`` once its
scheduled send time has come, calls ``ServeSystem.step`` while work is
pending, and otherwise sleeps until the next send time. Each token is
stamped with the wall clock when ``step`` returns it: the engine has then
pulled the token ids to the host, so the device has finished that step.
The cluster's virtual clock and ``summary()`` are never read.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

import spec
import weights
import workload

clock = time.perf_counter


@dataclasses.dataclass
class Sent:
    """One request of the window, as the client saw it."""
    req: workload.Request
    sched: float                  # scheduled send time (clock)
    submitted: float = -1.0
    rid: int = -1
    rejected: bool = False
    admitted: float = -1.0        # start of the round that admitted it
    stamps: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    finished: bool = False

    @property
    def plen(self) -> int:
        return len(self.req.prompt)


@dataclasses.dataclass
class Round:
    start: float
    end: float
    admitted: int                 # requests admitted (prefilled) this round
    contexts: List[int]           # keys attended by each live decode row


@dataclasses.dataclass
class Record:
    sent: List[Sent]
    rounds: List[Round]
    t0: float                     # window start (first send time's origin)
    t_end: float                  # window close
    t_drained: float
    compiles_in_window: int = 0
    trace_span: Optional[tuple] = None   # (start, stop) clock of the trace


class CompileCounter:
    """Counts XLA backend compiles while enabled."""

    def __init__(self):
        self.count = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, **_kw):
        if self.on and name.endswith("backend_compile_duration"):
            self.count += 1

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._event)


@dataclasses.dataclass
class Built:
    conf: Dict
    system: object
    params: Dict                  # the drawn weights, as placed, ...
    adapters: Dict                # ... which the reference reads after
    dims: Dict
    longest: Dict
    layout: object                # the configuration's layouts/<name>.py


def build(conf: Dict, traffic: Dict, seed: int) -> Built:
    """Weights and adapters from the seed, placed where they are served,
    and one ServeSystem over them."""
    from repro.core.adapter import AdapterPool
    from repro.models.model import abstract_params
    from repro.serving.api import build_system
    lay = spec.layout(conf)
    m = lay.dims(conf)
    longest = workload.longest_request(traffic)
    cfg = lay.model_config(conf)
    params, adapters = weights.make(conf, seed)
    want = jax.tree_util.tree_map(lambda s: (s.shape, str(s.dtype)),
                                  abstract_params(cfg))
    got = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), params)
    if want != got:
        raise RuntimeError(f"the program cannot serve layout "
                           f"{conf['layout']}: its weights are {got}, the "
                           f"program's {want}")
    pool = AdapterPool(cfg, m["n_adapters"], m["rank"], m["lora_scale"],
                       {t: dict(ab) for t, ab in adapters.items()})
    jax.block_until_ready((params, adapters))
    system = build_system(spec.serve_config(conf, longest), cfg,
                          params=params, pool=pool)
    return Built(conf, system, params, adapters, m, longest, lay)


def warm_up(b: Built, seed: int) -> None:
    """Run every decode bucket up to max_batch, and the prefill chunk
    geometries of the longest prompt (a shorter prompt uses a prefix of
    them), through the front door; then cancel the warm-up requests. Each
    adapter becomes resident on the way."""
    sys_ = b.system
    n_max = b.conf["serve"]["max_batch"]
    rng = np.random.default_rng(seed)
    plen, olen = b.longest["prompt"], b.longest["output"]
    handles, fill = [], 1
    while len(handles) < n_max:
        for _ in range(min(fill, n_max) - len(handles)):
            prompt = rng.integers(0, b.dims["vocab"], plen).tolist()
            handles.append(sys_.submit(prompt,
                                       adapter_id=len(handles)
                                       % b.dims["n_adapters"],
                                       max_new_tokens=olen))
        sys_.step()
        sys_.step()
        fill *= 2
    for h in handles:
        if h.state.name == "REJECTED":
            raise RuntimeError(f"warm-up request rejected: {h.error}")
        h.cancel()
    while not sys_.backend.idle():
        sys_.step()


def drive(b: Built, reqs: List[workload.Request], seconds: float,
          drain_s: float, trace_dir: Optional[str] = None,
          trace_s: float = 0.0,
          on_window_start: Optional[Callable[[float], None]] = None
          ) -> Record:
    """The measured window: ``reqs`` sent open-loop over ``seconds``, then
    stepping on until every request is done or ``drain_s`` has passed.
    With ``trace_dir``, the profiler records the window's last ``trace_s``
    seconds."""
    sys_ = b.system
    counter = CompileCounter()
    t0 = clock() + 0.05
    t_end = t0 + seconds
    if on_window_start is not None:
        on_window_start(t0)
    sent = [Sent(r, t0 + r.offset) for r in reqs]
    by_rid: Dict[int, Sent] = {}
    rounds: List[Round] = []
    trace_at = t_end - trace_s if trace_dir else None
    tracing, span = False, None
    nxt = 0
    counter.on = True
    try:
        while True:
            now = clock()
            if trace_at is not None and not tracing and now >= trace_at \
                    and span is None:
                jax.profiler.start_trace(trace_dir)
                tracing, span = True, (clock(), None)
            if tracing and now >= t_end:
                jax.profiler.stop_trace()
                tracing, span = False, (span[0], now)
                counter.on = False
            if now >= t_end:
                counter.on = False
            while nxt < len(sent) and sent[nxt].sched <= now:
                s = sent[nxt]
                with jax.profiler.TraceAnnotation("bench.submit"):
                    h = sys_.submit(s.req.prompt, adapter_id=s.req.adapter,
                                    max_new_tokens=s.req.output_len)
                s.submitted, s.rid = clock(), h.rid
                s.rejected = h.state.name == "REJECTED"
                if not s.rejected:
                    by_rid[h.rid] = s
                nxt += 1
            busy = not sys_.backend.idle()
            if busy:
                t_a = clock()
                with jax.profiler.TraceAnnotation("bench.step"):
                    evs = sys_.step()
                t_b = clock()
                contexts, n_adm = [], 0
                for ev in evs:
                    s = by_rid.get(ev.rid)
                    if s is None:
                        continue
                    if ev.kind == "prefill":
                        s.admitted = t_a
                        n_adm += 1
                    elif ev.kind == "token":
                        contexts.append(s.plen + len(s.tokens))
                        s.tokens.append(int(ev.token))
                        s.stamps.append(t_b)
                    elif ev.kind == "finished":
                        s.finished = True
                rounds.append(Round(t_a, t_b, n_adm, contexts))
            elif nxt >= len(sent):
                if not tracing:
                    break
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(max(0.0, min(t_end - clock(), 0.05)))
            else:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(max(0.0, min(sent[nxt].sched, t_end)
                                   - clock()))
            if clock() > t_end + drain_s:
                break
    finally:
        if tracing:
            jax.profiler.stop_trace()
            span = (span[0], clock())
        counter.close()
    return Record(sent, rounds, t0, t_end, clock(), counter.count, span)


def memory_peak_bytes(n_chips: int) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:n_chips]]
    return int(max(peaks))


def release(b: Built) -> None:
    """Close the system and drop what the program made, so that only the
    benchmark's own weights stay on the device."""
    b.system.close()
    b.system = None
    gc.collect()
