"""Load generation against the program's HTTP/SSE front end, client side.

A copy of the sound parts of ``paddle_tpu/serving/loadgen.py`` (the request
coroutine and the open-loop ``offer``), kept here so that a later change to
the program cannot move the yardstick, with two additions: every token
event's arrival time is recorded (TTFT is taken from the time a request was
DUE, and every gap between tokens is a sample), and a closed loop in which
each client sends its next request when the last completes.

All times are ``time.perf_counter()`` seconds, the clock the window uses.
The synchronous parts (building and writing a request, parsing an event) sit
inside ``TraceAnnotation``s so that a traced run can lay idle gaps of the
device against the generator's own work.
"""
from __future__ import annotations

import asyncio
import json
import time
from typing import List

import jax

DONE_REASONS_BAD = (None, "error", "connection_error", "connect_error")


async def one_request(host: str, port: int, payload: dict,
                      due: float) -> dict:
    """POST one streaming generate and consume its SSE events.

    The record: ``due`` and ``sent`` times, HTTP ``status``, the delivered
    ``token_ids``, ``arrivals`` (one ``(time, tokens in the event)`` per
    token event) and ``finish_reason`` (``None`` while the stream never
    ended cleanly)."""
    rec = {"due": due, "sent": None, "status": 0, "token_ids": [],
           "arrivals": [], "finish_reason": None,
           "prompt": payload["prompt"],
           "max_new_tokens": payload["max_new_tokens"]}
    try:
        reader, writer = await asyncio.open_connection(host, port)
    except OSError:
        rec["finish_reason"] = "connect_error"
        return rec
    try:
        with jax.profiler.TraceAnnotation("bench.client_send"):
            body = json.dumps(dict(payload, stream=True)).encode()
            writer.write(
                b"POST /v1/generate HTTP/1.1\r\nHost: bench\r\n"
                b"Content-Type: application/json\r\n"
                b"Content-Length: %d\r\n\r\n" % len(body) + body)
            rec["sent"] = time.perf_counter()
        await writer.drain()
        status_line = await reader.readline()
        parts = status_line.split()
        rec["status"] = int(parts[1]) if len(parts) > 1 else 0
        while True:                       # headers
            h = await reader.readline()
            if h in (b"\r\n", b"\n", b""):
                break
        if rec["status"] != 200:
            await reader.read()           # a shed or error body: one JSON doc
            return rec
        while True:
            line = await reader.readline()
            if not line:
                break
            line = line.strip()
            if not line.startswith(b"data: "):
                continue
            with jax.profiler.TraceAnnotation("bench.client_event"):
                ev = json.loads(line[6:])
                if ev.get("done"):
                    rec["finish_reason"] = ev.get("finish_reason")
                    break
                tokens = ev.get("tokens", ())
                if tokens:
                    rec["token_ids"].extend(tokens)
                    rec["arrivals"].append((time.perf_counter(),
                                            len(tokens)))
        return rec
    except (ConnectionResetError, ConnectionAbortedError, BrokenPipeError,
            asyncio.IncompleteReadError):
        rec["finish_reason"] = "connection_error"
        return rec
    finally:
        writer.close()


def completed(rec: dict) -> bool:
    """The stream was accepted, ended cleanly and delivered every token it
    was asked for."""
    return (rec["status"] == 200
            and rec["finish_reason"] not in DONE_REASONS_BAD
            and len(rec["token_ids"]) == rec["max_new_tokens"])


async def open_loop(host: str, port: int, plan, t0: float) -> List[dict]:
    """Send every ``(offset_seconds, payload)`` of ``plan`` at ``t0 +
    offset`` whatever the server does (a late server never delays a later
    arrival) and wait for every stream.  Records come back in plan order."""
    tasks = []
    for offset, payload in plan:
        delay = (t0 + offset) - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.ensure_future(
            one_request(host, port, payload, due=t0 + offset)))
    return list(await asyncio.gather(*tasks))


async def closed_loop(host: str, port: int, clients: int, payload_of,
                      t0: float, seconds: float) -> List[dict]:
    """One coroutine per client; each sends ``payload_of(client, k)`` as its
    k-th request when its last stream has ended, and starts none after
    ``t0 + seconds``.  A request is due when its client became free."""
    async def client(i):
        recs = []
        while True:
            now = time.perf_counter()
            if now - t0 >= seconds:
                return recs
            recs.append(await one_request(
                host, port, payload_of(i, len(recs)), due=now))
    per = await asyncio.gather(*(client(i) for i in range(clients)))
    return [r for recs in per for r in recs]


async def sample_while(task, sampler, every: float):
    """Call ``sampler()`` every ``every`` seconds until ``task`` ends;
    returns the task's result."""
    while not task.done():
        sampler()
        await asyncio.wait([task], timeout=every)
    return task.result()


# -- reduction of the records ---------------------------------------------------

def tokens_inside(recs, t0: float, t1: float) -> int:
    """Output tokens delivered to clients inside [t0, t1]."""
    return sum(k for r in recs for t, k in r["arrivals"] if t0 <= t <= t1)


def ttfts(recs, t_give_up: float) -> List[float]:
    """Seconds from each request's DUE time to its first token.  A request
    that never got one (failed, shed, cut) waited until ``t_give_up``: it
    misses every latency rather than vanishing from the tail."""
    return [((r["arrivals"][0][0] if r["arrivals"] else t_give_up)
             - r["due"]) for r in recs]


def gaps(recs) -> List[float]:
    """Every gap between consecutive tokens of every request, in seconds.
    Tokens that arrive in one event are 0 apart; the first of them carries
    the wait since the event before."""
    out = []
    for r in recs:
        for (t_prev, _), (t, k) in zip(r["arrivals"], r["arrivals"][1:]):
            out.append(t - t_prev)
            out.extend([0.0] * (k - 1))
        if r["arrivals"]:
            out.extend([0.0] * (r["arrivals"][0][1] - 1))
    return out


def lateness(recs) -> List[float]:
    """How late the generator sent each request: actual send minus due."""
    return [r["sent"] - r["due"] for r in recs if r["sent"] is not None]
