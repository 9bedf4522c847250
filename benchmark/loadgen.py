#!/usr/bin/env python3
"""The load generator: its own OS process, stdlib only, never imports JAX.

    python benchmark/loadgen.py --port P --traffic benchmark/traffic/X.json \
        --seed N --seconds S --t0 EPOCH --vocab V (--clients C | --rate R) \
        --out RECORD.json

The process that holds the chip runs only the program; this one builds
the request table from the seed BEFORE it sends anything, then sends the
ramp (``ramp_s`` of the same traffic, unmeasured) from ``--t0`` and the
measured window ``[t0 + ramp_s, t0 + ramp_s + seconds)`` right after it.
Open loop: seeded due times at ``--rate``; every latency is timed from
the DUE time and ``sent - due`` is recorded as the generator's lateness.
Closed loop: ``--clients`` connections, each sends its next request when
the last one ended (due = the moment it became free to send).  Nothing
is sent after the window's end, every request sent is awaited to its
end, none is cancelled.  ``/metrics`` and ``/healthz`` are scraped at
the window's two edges.  All clocks are ``time.time()`` so the harness
can lay the record beside the server's spans.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import traffic as traffic_mod  # noqa: E402

REQUEST_TIMEOUT_S = 300.0


async def http_get(host: str, port: int, path: str) -> tuple[int, str]:
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write(f"GET {path} HTTP/1.1\r\nHost: {host}:{port}\r\n"
                     "Connection: close\r\n\r\n".encode())
        await writer.drain()
        raw = await asyncio.wait_for(reader.read(), 30.0)
    finally:
        writer.close()
    head, _, body = raw.partition(b"\r\n\r\n")
    return int(head.split()[1]), body.decode(errors="replace")


async def post_completion(host: str, port: int, body: dict, rec: dict) -> None:
    """One completion; fills ``rec`` with status, token ids, the arrival
    time of every token and the finish reason.  A non-streamed answer's
    tokens all arrive with its body."""
    payload = json.dumps(body, separators=(",", ":")).encode()
    reader, writer = await asyncio.open_connection(host, port)
    try:
        rec["sent"] = time.time()
        writer.write(b"POST /v1/completions HTTP/1.1\r\n"
                     + f"Host: {host}:{port}\r\n".encode()
                     + b"Content-Type: application/json\r\n"
                     + f"Content-Length: {len(payload)}\r\n".encode()
                     + b"Connection: close\r\n\r\n" + payload)
        await writer.drain()
        status_line = await reader.readline()
        if not status_line:
            raise ConnectionError("closed before any response byte")
        rec["status"] = int(status_line.split()[1])
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass
        if rec["status"] != 200:
            rec["error"] = (await reader.read()).decode(errors="replace")[:300]
            return
        tokens, times = rec["tokens"], rec["times"]
        if not body["stream"]:
            obj = json.loads(await reader.read())
            choice = obj["choices"][0]
            now = time.time()
            tokens.extend(choice.get("token_ids", []))
            times.extend([now] * len(tokens))
            rec["finish"] = choice.get("finish_reason")
            return
        while True:
            line = await reader.readline()
            if not line:
                return
            if not line.startswith(b"data:"):
                continue
            now = time.time()
            data = line[5:].strip()
            if data == b"[DONE]":
                return
            choice = json.loads(data)["choices"][0]
            if choice.get("token_id") is not None:
                tokens.append(choice["token_id"])
                times.append(now)
            if choice.get("finish_reason"):
                rec["finish"] = choice["finish_reason"]
    finally:
        writer.close()


class Run:
    def __init__(self, args, traffic: dict) -> None:
        self.args, self.traffic = args, traffic
        self.ramp_s = float(traffic.get("ramp_s", 0.0))
        self.w0 = args.t0 + self.ramp_s
        self.w1 = self.w0 + args.seconds
        self.records: list[dict] = []
        self.scrapes: dict = {}
        self.wrapped = False

    def sessions(self, n: int) -> list[list[dict]]:
        table = traffic_mod.request_table(self.traffic, self.args.seed, n,
                                          self.args.vocab)
        out: dict[int, list[dict]] = {}
        for r in table:
            out.setdefault(r["session"], []).append(r)
        return list(out.values())

    async def one(self, req: dict, due: float, history: list[int],
                  cut: float | None = None) -> dict:
        prompt = history + req["prompt"]
        max_tokens = req["max_tokens"]
        if cut is not None:
            max_tokens = max(1, int(round(cut * max_tokens)))
        rec = dict(idx=req["idx"], session=req["session"], turn=req["turn"],
                   prompt_len=len(prompt), max_tokens=max_tokens,
                   stream=req["stream"], due=due, sent=None, status=None,
                   finish=None, error=None, tokens=[], times=[],
                   ramp_cut=cut is not None)
        self.records.append(rec)
        body = {"prompt": prompt, "max_tokens": max_tokens,
                "stream": req["stream"], "seed": req["idx"]}
        if self.args.model:
            body["model"] = self.args.model
        try:
            await asyncio.wait_for(
                post_completion(self.args.host, self.args.port, body, rec),
                REQUEST_TIMEOUT_S)
        except (OSError, asyncio.IncompleteReadError, asyncio.TimeoutError,
                ValueError, KeyError) as e:
            rec["error"] = f"{type(e).__name__}: {e}"
        rec["end"] = time.time()
        # the prompt is rebuilt from the seed by the harness; keep the
        # record small
        history[:] = prompt + rec["tokens"]
        return rec

    async def session(self, turns: list[dict], due: float,
                      cut: float | None = None) -> None:
        history: list[int] = []
        for req in turns:
            if req["turn"]:
                due = time.time() + req["think_s"]
                if due >= self.w1:
                    return
                await asyncio.sleep(max(0.0, due - time.time()))
            rec = await self.one(req, due, history, cut)
            cut = None
            if rec["error"] or rec["status"] != 200:
                return

    async def scrape(self, at: float, name: str) -> None:
        await asyncio.sleep(max(0.0, at - time.time()))
        out = {"t": time.time()}
        for path in ("/metrics", "/healthz"):
            try:
                status, text = await http_get(self.args.host, self.args.port, path)
                out[path] = {"status": status, "text": text}
            except OSError as e:
                out[path] = {"status": None, "text": "", "error": str(e)}
        self.scrapes[name] = out

    async def closed_loop(self) -> None:
        clients = self.args.clients
        sessions = self.sessions(max(1024, clients * 64))
        cuts = traffic_mod.phase_fractions(
            clients, random.Random(self.args.seed + 1))
        nxt = iter(range(10**9))

        async def client(i: int) -> None:
            cut: float | None = cuts[i]
            while time.time() < self.w1:
                k = next(nxt)
                if k >= len(sessions):
                    self.wrapped = True
                await self.session(sessions[k % len(sessions)], time.time(), cut)
                cut = None

        await asyncio.sleep(max(0.0, self.args.t0 - time.time()))
        await asyncio.gather(*(client(i) for i in range(clients)))

    async def open_loop(self) -> None:
        span = self.ramp_s + self.args.seconds
        n = int(self.args.rate * span * 1.25) + 2 * traffic_mod.DEFAULT_BLOCK
        sessions = self.sessions(n)
        dues = traffic_mod.arrival_times(self.traffic, self.args.seed,
                                         self.args.rate, len(sessions))
        tasks = []
        for turns, rel in zip(sessions, dues):
            due = self.args.t0 + rel
            if due >= self.w1:
                break
            await asyncio.sleep(max(0.0, due - time.time()))
            tasks.append(asyncio.create_task(self.session(turns, due)))
        else:
            self.wrapped = True  # the table ran out before the window did
        await asyncio.gather(*tasks)

    async def main(self) -> dict:
        scrapes = [asyncio.create_task(self.scrape(self.w0, "start")),
                   asyncio.create_task(self.scrape(self.w1, "end"))]
        await (self.closed_loop() if self.traffic["loop"] == "closed"
               else self.open_loop())
        await asyncio.gather(*scrapes)
        return dict(
            t0=self.args.t0, window=[self.w0, self.w1], loop=self.traffic["loop"],
            clients=self.args.clients, rate_rps=self.args.rate,
            seed=self.args.seed, table_wrapped=self.wrapped,
            finished_at=time.time(), requests=self.records, scrapes=self.scrapes)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--t0", type=float, required=True,
                    help="epoch seconds at which the ramp starts")
    ap.add_argument("--vocab", type=int, required=True)
    ap.add_argument("--clients", type=int, default=0)
    ap.add_argument("--rate", type=float, default=0.0)
    ap.add_argument("--model", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    traffic = traffic_mod.load_traffic(args.traffic)
    if traffic["loop"] == "closed" and args.clients < 1:
        ap.error("a closed loop needs --clients")
    if traffic["loop"] == "open" and args.rate <= 0:
        ap.error("an open loop needs --rate")
    record = asyncio.run(Run(args, traffic).main())
    tmp = args.out + ".tmp"
    with open(tmp, "w") as f:
        json.dump(record, f)
    os.replace(tmp, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
