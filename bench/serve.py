"""Drives a closed loop of streaming clients against a loaded engine.

Each client is a thread that sends its requests one after another
through ``chat_completions_create(..., stream=True)`` and stamps every
streamed chunk with the host's monotonic clock as it arrives.  Clients
start one at a time, each once the previous one has its first token, so
the ramp to a full batch runs the same steps on every seed.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import List, Optional

from bench.traffic import Request

clock = time.perf_counter


@dataclass
class Record:
    req: Request
    t_submit: float = 0.0
    chunks: List[tuple] = field(default_factory=list)   # (t, n_tokens)
    text: str = ""
    finish: Optional[str] = None
    error: Optional[str] = None

    @property
    def tokens(self) -> int:
        return sum(n for _, n in self.chunks)

    @property
    def done(self) -> bool:
        return self.finish is not None or self.error is not None


class ClosedLoop:
    def __init__(self, engine, model: str, plans: List[List[Request]],
                 tokenizer):
        self.engine, self.model, self.tok = engine, model, tokenizer
        self.plans = plans
        self.records: List[Record] = []
        self.current: List[Optional[Record]] = [None] * len(plans)
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._first = [threading.Event() for _ in plans]
        self._threads = [threading.Thread(target=self._client, args=(i,),
                                          name=f"bench-client-{i}",
                                          daemon=True)
                         for i in range(len(plans))]

    def _client(self, i: int):
        for req in self.plans[i]:
            if self._stop.is_set():
                return
            rec = Record(req)
            with self._lock:
                self.records.append(rec)
                self.current[i] = rec
            rec.t_submit = clock()
            try:
                stream = self.engine.chat_completions_create({
                    "model": self.model, "stream": True,
                    "messages": [{"role": "user",
                                  "content": self.tok.decode(req.prompt)}],
                    "max_tokens": req.max_tokens,
                    "temperature": req.temperature, "top_p": req.top_p,
                    "seed": req.seed}, request_id=req.rid)
                for chunk in stream:
                    t = clock()
                    ch = chunk.choices[0] if chunk.choices else None
                    if ch is None:
                        continue
                    text = ch.delta.content or ""
                    if text:
                        rec.chunks.append((t, len(text)))
                        rec.text += text
                        self._first[i].set()
                    if ch.finish_reason:
                        rec.finish = ch.finish_reason
                rec.finish = rec.finish or "end"
            except Exception as e:            # a failed request is counted
                rec.error = f"{type(e).__name__}: {e}"
                self._first[i].set()

    def start(self, timeout: float) -> float:
        """Start the clients one at a time; returns once every client's
        first request is decoding, with the time it did."""
        for i, th in enumerate(self._threads):
            th.start()
            if not self._first[i].wait(timeout):
                raise TimeoutError(f"client {i}: no first token in "
                                   f"{timeout} s")
        return clock()

    def wait_until(self, cond, timeout: float) -> bool:
        end = clock() + timeout
        while not cond():
            if clock() > end:
                return False
            time.sleep(0.05)
        return True

    def in_flight(self) -> List[Record]:
        with self._lock:
            return [r for r in self.current if r is not None and not r.done]

    def stop(self, timeout: float = 60.0):
        """No further requests; abort those in flight and join clients."""
        self._stop.set()
        end = clock() + timeout
        # a client may submit once more between its check and ours
        while any(th.is_alive() for th in self._threads):
            for r in self.in_flight():
                self.engine.abort(r.req.rid)
            if clock() > end:
                raise TimeoutError("clients did not end")
            time.sleep(0.05)
