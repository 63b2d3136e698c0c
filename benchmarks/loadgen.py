#!/usr/bin/env python3
"""The load generator: one process, one thread (asyncio), started by the
serve runner so that it shares no interpreter lock with the cluster's
driver.  It reads a plan (the generator's schedule, the URL, the window's
length), sends set-up traffic (warm-up of every program, then priming),
announces when the window will open, runs the schedule against the HTTP
endpoint with streamed completions, and writes one record per request.

All times in the records are seconds relative to the window's first instant
on this process's monotonic clock; ``window.json`` carries the wall-clock
time of that instant for the other processes.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time

import aiohttp

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmarks import common  # noqa: E402


class Client:
    def __init__(self, url: str, model: str, seconds: float):
        self.url, self.model, self.seconds = url, model, seconds
        self.t0 = None  # monotonic instant the window opens
        self.session = None
        self.records = []

    def now(self) -> float:
        return time.monotonic() - self.t0

    async def complete(self, req: dict) -> dict:
        """One streamed greedy completion.  Returns its record."""
        body = {"model": self.model, "prompt": req["prompt"],
                "max_tokens": req["max_tokens"], "temperature": 0.0,
                "ignore_eos": True, "stream": True}
        rec = {"id": req.get("id"), "due": req.get("due_s"),
               "prompt_len": len(req["prompt"]),
               "max_tokens": req["max_tokens"], "first": None, "last": None,
               "n_out": 0, "n_in_window": 0, "ok": False, "error": None,
               "sent": self.now() if self.t0 is not None else None}
        for key in ("session", "turn", "tenant", "warm"):
            if key in req:
                rec[key] = req[key]
        try:
            async with self.session.post(self.url, json=body) as resp:
                if resp.status != 200:
                    rec["error"] = f"HTTP {resp.status}: " \
                        f"{(await resp.text())[:200]}"
                    return rec
                async for line in resp.content:
                    if not line.startswith(b"data: "):
                        continue
                    data = line[6:].strip()
                    if data == b"[DONE]":
                        break
                    event = json.loads(data)
                    if "error" in event:
                        rec["error"] = str(event["error"])[:200]
                        return rec
                    choice = event["choices"][0]
                    if choice["finish_reason"] is not None:
                        rec["finish_reason"] = choice["finish_reason"]
                        continue
                    if self.t0 is not None:
                        t = self.now()
                        if rec["first"] is None:
                            rec["first"] = t
                        rec["last"] = t
                        if 0.0 <= t < self.seconds:
                            rec["n_in_window"] += 1
                    rec["n_out"] += 1
            rec["ok"] = rec["n_out"] == req["max_tokens"]
            if not rec["ok"] and rec["error"] is None:
                rec["error"] = f"{rec['n_out']} of {req['max_tokens']} tokens"
        except asyncio.CancelledError:
            rec["error"] = "cancelled"
            rec["cancelled"] = True
            raise
        except Exception as e:  # noqa: BLE001 - a failed request is a result
            rec["error"] = f"{type(e).__name__}: {e}"[:200]
        finally:
            self.records.append(rec)
        return rec


async def setup_traffic(cl: Client, plan: dict) -> dict:
    """Warm-up (one at a time: each compiles or loads a program) and then
    priming (8 at a time)."""
    cl.records = []
    t = time.monotonic()
    for req in plan["schedule"]["warmup"]:
        rec = await cl.complete(req)
        if not rec["ok"]:
            raise RuntimeError(f"warm-up {req.get('warm')} failed: "
                               f"{rec['error']}")
    warm_s = time.monotonic() - t
    t = time.monotonic()
    gate = asyncio.Semaphore(8)

    async def one(req):
        async with gate:
            return await cl.complete(req)

    primed = await asyncio.gather(*[one(r) for r in
                                    plan["schedule"]["prime"]])
    bad = [r for r in primed if not r["ok"]]
    if bad:
        raise RuntimeError(f"priming failed: {bad[0]['error']}")
    return {"warmup_s": warm_s, "prime_s": time.monotonic() - t,
            "warmup_requests": len(plan["schedule"]["warmup"]),
            "prime_requests": len(primed)}


async def open_loop(cl: Client, sched: dict) -> None:
    async def one(req):
        delay = req["due_s"] - cl.now()
        if delay > 0:
            await asyncio.sleep(delay)
        await cl.complete(req)

    tasks = [asyncio.ensure_future(one(r)) for r in sched["requests"]]
    limit = cl.seconds + sched["drain_s"] - cl.now()
    done, pending = await asyncio.wait(tasks, timeout=max(0.0, limit))
    for t in pending:  # still open after the drain: they count as failed
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    for t in done:
        t.result()


async def closed_loop(cl: Client, sched: dict) -> None:
    errors = []

    async def client(reqs):
        for req in reqs:
            if cl.now() >= cl.seconds:
                return
            req["due_s"] = cl.now()  # due when the last one finished
            rec = await cl.complete(req)
            if not rec["ok"]:
                errors.append(rec["error"])
                await asyncio.sleep(0.1)  # a failing server: no hot loop
        raise RuntimeError(f"a client ran out of prepared requests (raise "
                           f"per_client in the traffic mix); request "
                           f"errors so far: {errors[:3]}")

    tasks = [asyncio.ensure_future(client(reqs)) for reqs in sched["clients"]]
    await asyncio.sleep(max(0.0, cl.seconds - cl.now()))
    for t in tasks:  # the window is over: what is in flight is cut off
        t.cancel()
    for r in await asyncio.gather(*tasks, return_exceptions=True):
        if isinstance(r, Exception) and not isinstance(
                r, asyncio.CancelledError):
            raise r


async def main(plan_path: str) -> None:
    with open(plan_path) as f:
        plan = json.load(f)
    sched, out = plan["schedule"], plan["out_dir"]
    cl = Client(plan["url"], plan["model"], float(plan["seconds"]))
    timeout = aiohttp.ClientTimeout(total=None, sock_read=120)
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout,
                                     connector=conn) as session:
        cl.session = session
        setup = await setup_traffic(cl, plan)
        setup_records, cl.records = cl.records, []
        lead = float(sched.get("lead_s") or 0.0)
        if sched["mode"] == "open" and sched["requests"]:
            lead = max(lead, -sched["requests"][0]["due_s"])
        cl.t0 = time.monotonic() + lead + 0.25
        common.write_json(os.path.join(out, "window.json"), {
            "t0_wall": time.time() + (cl.t0 - time.monotonic()),
            "seconds": cl.seconds, **setup})
        await (open_loop if sched["mode"] == "open" else closed_loop)(
            cl, sched)
    common.write_json(os.path.join(out, "loadgen.json"), {
        "records": cl.records, "setup_records": setup_records,
        "ended": time.monotonic() - cl.t0})


if __name__ == "__main__":
    asyncio.run(main(sys.argv[1]))
