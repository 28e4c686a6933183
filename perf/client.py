"""The load generator: one process, one thread, asyncio + aiohttp.

Sends a plan (see ``perf/generators``) to an OpenAI ``/v1/completions``
endpoint as token-id prompts with SSE streaming, ``temperature 0``,
``ignore_eos`` and exact ``max_tokens``, and records for every request when
it was due, when it was sent, and when each event that carried at least one
token arrived. Open-loop requests are timed from when they were *due*.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import time
from typing import List, Optional

import aiohttp

from . import tokenizer


@dataclasses.dataclass
class Record:
    due: float  # seconds from the window's start
    sent: float = 0.0
    events: List[float] = dataclasses.field(default_factory=list)
    event_tokens: List[int] = dataclasses.field(default_factory=list)
    want_tokens: int = 0
    done: Optional[float] = None  # completed, all tokens received
    error: Optional[str] = None
    generated: List[int] = dataclasses.field(default_factory=list)

    @property
    def n_tokens(self) -> int:
        return sum(self.event_tokens)


class Session:
    def __init__(self, tokens: list):
        self.tokens = list(tokens)
        self.busy = False
        self.idle_since = 0.0


async def _stream(http, base: str, model: str, prompt: list, rec: Record,
                  t0: float) -> None:
    body = {"model": model, "prompt": prompt, "max_tokens": rec.want_tokens,
            "temperature": 0.0, "ignore_eos": True, "stream": True}
    rec.sent = time.monotonic() - t0
    try:
        async with http.post(f"{base}/v1/completions", json=body) as resp:
            if resp.status != 200:
                rec.error = f"HTTP {resp.status}: {(await resp.text())[:200]}"
                return
            finished = False
            async for raw in resp.content:
                if not raw.startswith(b"data:"):
                    continue
                now = time.monotonic() - t0
                payload = raw[5:].strip()
                if payload == b"[DONE]":
                    finished = True
                    break
                frame = json.loads(payload)
                if "error" in frame:
                    rec.error = f"error frame: {str(frame['error'])[:200]}"
                    return
                ids = tokenizer.ids_of(frame["choices"][0].get("text", ""))
                if ids:
                    rec.events.append(now)
                    rec.event_tokens.append(len(ids))
                    rec.generated.extend(ids)
            if not finished:
                rec.error = "stream ended without [DONE]"
            elif rec.n_tokens != rec.want_tokens:
                rec.error = (f"asked for {rec.want_tokens} tokens, counted "
                             f"{rec.n_tokens}")
            else:
                rec.done = time.monotonic() - t0
    except asyncio.CancelledError:
        raise
    except Exception as e:  # noqa: BLE001 — any transport failure is a failed request
        rec.error = f"{type(e).__name__}: {e}"[:200]


async def _run_plan(base: str, model: str, plan: dict, sessions: list,
                    seconds: float, drain: bool = False) -> list:
    records: List[Record] = []
    tasks = set()
    freed = asyncio.Event()
    timeout = aiohttp.ClientTimeout(total=None, sock_connect=30)
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as http:
        t0 = time.monotonic()

        async def one(req: dict, rec: Record, sess: Optional[Session]):
            prompt = (sess.tokens + req["append"]) if sess else req["prompt"]
            try:
                await _stream(http, base, model, prompt, rec, t0)
            finally:
                if sess is not None:
                    if rec.done is not None:
                        sess.tokens = prompt + rec.generated
                    sess.busy = False
                    sess.idle_since = time.monotonic() - t0
                    freed.set()

        def launch(req, sess=None):
            rec = Record(due=req["due"], want_tokens=req["max_tokens"])
            records.append(rec)
            task = asyncio.ensure_future(one(req, rec, sess))
            tasks.add(task)
            task.add_done_callback(tasks.discard)
            return task

        async def open_loop():
            for req in plan["requests"]:
                delay = req["due"] - (time.monotonic() - t0)
                if delay > 0:
                    await asyncio.sleep(delay)
                sess = None
                if "append" in req:
                    while True:
                        idle = [s for s in sessions if not s.busy]
                        if idle:
                            break
                        freed.clear()
                        await freed.wait()
                    sess = min(idle, key=lambda s: s.idle_since)
                    sess.busy = True
                launch(req, sess)

        async def closed_client(queue: list, sess: Optional[Session]):
            while queue:
                req = dict(queue.pop(0))
                req["due"] = time.monotonic() - t0
                if sess is not None:
                    sess.busy = True
                await asyncio.wait({launch(req, sess)})  # the driver's
                # cancellation must not reach the request it waits for

        if plan["mode"] == "open":
            drivers = [asyncio.ensure_future(open_loop())]
        else:
            queue = list(plan["requests"])
            drivers = [asyncio.ensure_future(closed_client(
                queue, sessions[k] if sessions else None))
                for k in range(plan["clients"])]
        await asyncio.wait(drivers, timeout=max(seconds - (time.monotonic() - t0), 0))
        remaining = seconds - (time.monotonic() - t0)
        if remaining > 0:  # schedule exhausted early: let the window run out
            if tasks:
                await asyncio.wait(list(tasks), timeout=remaining)
            remaining = seconds - (time.monotonic() - t0)
            if remaining > 0 and plan["mode"] == "open":
                await asyncio.sleep(remaining)
        closed_at = time.monotonic() - t0
        if drain:  # warm-up: let what was sent run to its end (it may compile)
            for d in drivers:
                d.cancel()
            while tasks:
                await asyncio.wait(list(tasks))
        pending = [t for t in list(drivers) + list(tasks) if not t.done()]
        for t in pending:
            t.cancel()
        if pending:
            await asyncio.gather(*pending, return_exceptions=True)
    for rec in records if not drain else []:
        # What happened after the window closed is not part of it.
        if rec.done is not None and rec.done > seconds:
            rec.done = None
        keep = [i for i, t in enumerate(rec.events) if t <= seconds]
        rec.events = [rec.events[i] for i in keep]
        rec.event_tokens = [rec.event_tokens[i] for i in keep]
    return records, closed_at


def run_plan(base: str, model: str, plan: dict, sessions: list,
             seconds: float, drain: bool = False):
    """Run ``plan`` for ``seconds``; returns (records, closed_at). Requests
    in flight at the close are cancelled, or with ``drain`` awaited."""
    return asyncio.run(_run_plan(base, model, plan, sessions, seconds, drain))


async def _send_together(base: str, model: str, prompts: list, max_tokens: int,
                         blocker: Optional[list], lead_s: float) -> list:
    records = [Record(due=0.0, want_tokens=max_tokens) for _ in prompts]
    timeout = aiohttp.ClientTimeout(total=None, sock_connect=30)
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as http:
        t0 = time.monotonic()
        first = []
        if blocker:
            records.append(Record(due=0.0, want_tokens=1))
            first.append(asyncio.ensure_future(
                _stream(http, base, model, blocker, records[-1], t0)))
            await asyncio.sleep(lead_s)
        await asyncio.gather(*first, *(_stream(http, base, model, p, r, t0)
                                       for p, r in zip(prompts, records)))
    return records


def send_together(base: str, model: str, prompts: list, max_tokens: int = 1,
                  blocker: Optional[list] = None, lead_s: float = 0.0) -> list:
    """Send ``prompts`` at the same instant and wait for all of them (the
    warm-up's probes); returns their records. With ``blocker``, that prompt
    goes ``lead_s`` seconds ahead: the engine is inside its one long
    prefill step while the others arrive, so they are scheduled together."""
    return asyncio.run(_send_together(base, model, prompts, max_tokens,
                                      blocker, lead_s))


def percentile(values: list, q: float) -> float:
    """Linear-interpolated percentile (q in 0..100) of a non-empty list."""
    v = sorted(values)
    if len(v) == 1:
        return v[0]
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def summarize(records: list, seconds: float) -> dict:
    """The window's client-side numbers. TTFT is from the due time."""
    in_window = [r for r in records if r.due < seconds]
    failed = [r for r in in_window if r.error is not None]
    completed = [r for r in in_window if r.done is not None]
    ttft = [(r.events[0] - r.due) * 1e3 for r in in_window
            if r.events and r.error is None]
    gaps = [(b - a) * 1e3 for r in in_window if r.error is None
            for a, b in zip(r.events, r.events[1:])]
    late = [(r.sent - r.due) * 1e3 for r in in_window]
    return {
        "attempted": len(completed) + len(failed),
        "failed": len(failed),
        "completed": len(completed),
        "in_flight_at_close": len(in_window) - len(completed) - len(failed),
        "ttft_ms": ttft,
        "gap_ms": gaps,
        "generator_late_ms": late,
        "output_tokens_completed": sum(r.want_tokens for r in completed),
        "output_tokens_streamed": sum(r.n_tokens for r in in_window
                                      if r.error is None),
        "errors": sorted({r.error for r in failed})[:5],
    }
