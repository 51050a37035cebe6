"""Drives the serving engine with a cell's traffic and times it.

The entry is the program's own: ``NanoQuantModel(...).engine(...)``, then
``InferenceEngine.submit`` and ``step``. The harness timestamps every
token with its own clock in the engine's ``on_token`` callback, which
the engine calls at the end of the tick that produced the token, and
every request from the time it was due.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np

from bench.lib import model as bmodel
from bench.lib import traffic as btraffic

IDLE_POLL_S = 0.0005


@dataclasses.dataclass
class Record:
    item: btraffic.Item
    prompt: np.ndarray
    due: float                      # harness clock: when it was due
    submitted: float = 0.0
    handle: object = None
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    decode_ctx: List[int] = dataclasses.field(default_factory=list)

    @property
    def status(self) -> str:
        return self.handle.status if self.handle is not None else "unsent"


@dataclasses.dataclass
class StepLog:
    """Per engine tick: host start and end, and the decode work it did."""
    start: float
    end: float
    decode_tokens: int
    kv_rows: int                    # sum over decoded slots of rows read


def serving_mesh(chips: int):
    """The program's tensor-parallel serving mesh over the cell's chips
    (``launch.mesh.make_serving_mesh``); None for one chip."""
    if chips <= 1:
        return None
    from repro.launch.mesh import make_serving_mesh
    return make_serving_mesh(chips)


def build_engine(conf: dict, cellp: dict, seed: int, mesh=None):
    """The cell's engine over packed weights drawn from the seed; with a
    mesh, the weights drawn into their shards and the engine serving
    tensor-parallel over it."""
    from repro import api
    from repro.core.pipeline import QuantConfig
    from repro.serve.engine import ServeConfig
    cfg = bmodel.model_config(conf)
    params = bmodel.make_weights(conf, seed, mesh)
    nq = api.NanoQuantModel(params, cfg,
                            QuantConfig(target_bpw=conf["target_bpw"]), {})
    scfg = ServeConfig(greedy=True, page_size=cellp["page_size"],
                       kv_pool_pages=cellp.get("kv_pool_pages"),
                       page_watermark=cellp.get("page_watermark", 0))
    return nq.engine(scfg, max_batch=cellp["max_batch"],
                     max_len=cellp["max_len"], seed=0, mesh=mesh)


def warm_lengths(mix: dict, cellp: dict) -> List[int]:
    """One prompt length per prefill bucket the cell can use: every
    bucket of the mix's prompt lengths, and, where the pool is
    overcommitted, every bucket a preempted request's re-prefill (its
    prompt and the tokens it emitted) can reach."""
    from repro.serve.scheduler import bucket_length
    max_len = cellp["max_len"]
    top = btraffic.max_prompt(mix)
    if cellp.get("kv_pool_pages"):
        top = max_len - 1
    buckets = sorted({bucket_length(n, max_len)
                      for n in range(btraffic.min_prompt(mix), top + 1)})
    return [min(b, max_len - 2) for b in buckets]


def warm(engine, lengths: List[int]) -> None:
    """Compile every prefill bucket and the decode step, then drop what
    the warm-up left in the prefix index and the counters. Warm prompts
    are distinct, so none hits another's prefix."""
    from repro.serve.scheduler import Request
    vocab = engine.cfg.vocab_size
    handles = []
    for i, n in enumerate(lengths):
        prompt = ((np.arange(n) * 7 + i * 31 + 1) % vocab).astype(np.int32)
        handles.append(engine.submit(Request(-1 - i, prompt,
                                             max_new_tokens=2)))
    engine.run()
    for h in handles:
        if h.status != "done":
            raise RuntimeError(f"warm-up request of {h.request.prompt.size} "
                               f"tokens ended {h.status}: {h.error}")
    if not engine.stats["decode_traces"]:
        raise RuntimeError("warm-up did not compile the decode step")
    if engine.prefix is not None:
        engine.prefix.clear()
    engine.clear_finished()
    engine.reset_stats()


class Driver:
    """Submits the cell's requests and steps the engine, on its clock."""

    def __init__(self, engine, items: List[btraffic.Item], seed: int,
                 clients: Optional[int], clock=time.perf_counter):
        self.engine = engine
        self.items = items
        self.seed = seed
        self.clients = clients
        self.clock = clock
        self.records: Dict[int, Record] = {}
        self.steps: List[StepLog] = []
        self._next = 0
        self._started = 0           # closed loop: clients sending so far
        self._step_tokens = 0
        self._step_rows = 0

    # -- per token, called by the engine at the end of a tick --------------
    def _on_token(self, uid, token):
        rec = self.records[uid]
        i = len(rec.tokens)
        rec.times.append(self.clock())
        rec.tokens.append(int(token))
        if i > 0:                   # token 0 comes from the prefill
            rows = rec.item.prompt_len + i
            rec.decode_ctx.append(rows)
            self._step_tokens += 1
            self._step_rows += rows

    def submit(self, item: btraffic.Item, due: float) -> Record:
        from jax.profiler import TraceAnnotation
        from repro.serve.scheduler import Request
        vocab = self.engine.cfg.vocab_size
        prompt = btraffic.prompt_tokens(self.seed, item, vocab)
        rec = Record(item, prompt, due)
        self.records[item.index] = rec
        with TraceAnnotation("bench.submit"):
            rec.submitted = self.clock()
            rec.handle = self.engine.submit(
                Request(item.index, prompt, max_new_tokens=item.output_len),
                on_token=self._on_token)
        return rec

    def step(self) -> None:
        from jax.profiler import TraceAnnotation
        self._step_tokens = self._step_rows = 0
        t0 = self.clock()
        with TraceAnnotation("bench.step"):
            finished = self.engine.step()
        self.steps.append(StepLog(t0, self.clock(), self._step_tokens,
                                  self._step_rows))
        if self.clients:
            for _ in finished:
                self._send_next(self.clock())

    def ramp(self, steps: int) -> None:
        """Closed loop, in set-up: start the clients one after another
        over `steps` engine ticks, each sending its next request as the
        last ends, so the window opens on requests at every stage and
        not on one group admitted together."""
        if not self.clients or steps <= 0:
            return
        for k in range(steps):
            while self._started < self.clients and \
                    self._started * steps < (k + 1) * self.clients:
                self._send_next(self.clock())
                self._started += 1
            self.step()

    def _send_next(self, now: float) -> None:
        if self._next < len(self.items):
            self.submit(self.items[self._next], now)
            self._next += 1

    def _send_due(self, t0: float, now: float) -> None:
        """Open loop: submit every request due by `now`, stamped with
        the time it was due."""
        while self._next < len(self.items) and \
                t0 + self.items[self._next].due_s <= now:
            it = self.items[self._next]
            self.submit(it, t0 + it.due_s)
            self._next += 1

    def run(self, seconds: float, hooks=(), on_close=None,
            hold_s: float = 60.0) -> tuple:
        """Measure for `seconds`. Closed loop: `clients` requests in
        flight, each replaced as it ends (those `ramp` did not start
        start as the window opens). Open loop: every request
        submitted at its due time. Returns (t0, t1) of the window. For
        an open loop, stepping goes on past the close (arrivals too)
        until every request due in the window has its first token, for
        at most `hold_s` seconds. Each `(offset_s, fn)` of `hooks` is
        called as `fn(t)` once the window is `offset_s` old; `on_close(t)`
        as the window closes, before that hold."""
        from jax.profiler import TraceAnnotation
        eng = self.engine
        t0 = self.clock()
        t_close = t0 + seconds
        while self.clients and self._started < self.clients:
            self._send_next(t0)
            self._started += 1
        pending = sorted(hooks, key=lambda h: h[0])
        while True:
            now = self.clock()
            while pending and now >= t0 + pending[0][0]:
                pending.pop(0)[1](now)
                now = self.clock()
            if now >= t_close:
                break
            if not self.clients:
                self._send_due(t0, now)
            if eng.in_flight:
                self.step()
            else:
                with TraceAnnotation("bench.wait_arrival"):
                    nxt = (t0 + self.items[self._next].due_s
                           if not self.clients and self._next < len(self.items)
                           else t_close)
                    time.sleep(max(0.0, min(nxt, t_close) - now, IDLE_POLL_S))
        t1 = self.clock()
        if on_close is not None:
            on_close(t1)
        if not self.clients:
            due_in = [r for r in self.records.values() if r.due < t1]
            t_hold = t1 + hold_s
            while any(not r.times and not r.handle.finished for r in due_in) \
                    and self.clock() < t_hold:
                self._send_due(t0, self.clock())
                self.step()
        return t0, t1
