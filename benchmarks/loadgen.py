#!/usr/bin/env python3
"""The load generator: a process of its own that never imports jax (nor
numpy), so that it shares neither the chip nor the interpreter lock with
the decode thread it measures.  One thread, asyncio, raw HTTP/1.1.

    python loadgen.py --url http://127.0.0.1:PORT --schedule in.json \
        --out out.json --mode open|closed|serial --start-at T \
        --seconds S [--clients N] [--drain S]

Times are `time.monotonic()`, which on Linux is one clock for every
process of the machine, so the parent can place them in its window.
open: request i is sent at start + due_s[i], whatever came before.
closed: N clients take the next request of the list as soon as their last
one ended, until the window closes.  serial: one after another (warm-up).
After start + seconds (+ drain) whatever still streams is cut off.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import time
from urllib.parse import urlparse


async def one_request(host, port, req, rec):
    """POST /generate with stream=true and stamp each token as it comes."""
    body = json.dumps({"prompt": req["prompt"],
                       "max_new_tokens": req["max_new"],
                       "stream": True}).encode()
    rec["sent"] = time.monotonic()
    writer = None
    try:
        reader, writer = await asyncio.open_connection(host, port)
        writer.write(b"POST /generate HTTP/1.1\r\nHost: x\r\n"
                     b"Content-Type: application/json\r\n"
                     b"Content-Length: %d\r\n\r\n" % len(body) + body)
        await writer.drain()
        status = await reader.readline()
        rec["status"] = int(status.split()[1])
        headers = {}
        while True:
            line = await reader.readline()
            if line in (b"\r\n", b""):
                break
            k, _, v = line.decode().partition(":")
            headers[k.strip().lower()] = v.strip()
        if rec["status"] != 200:
            n = int(headers.get("content-length", 0))
            rec["error"] = (await reader.readexactly(n)).decode()[:200]
            return
        while True:                      # chunked transfer, one SSE a chunk
            size = int((await reader.readline()).strip() or b"0", 16)
            if size == 0:
                break
            data = await reader.readexactly(size + 2)
            now = time.monotonic()
            for ev in data[:-2].split(b"\n\n"):
                if not ev.startswith(b"data: "):
                    continue
                msg = json.loads(ev[6:])
                if "token" in msg:
                    rec["tokens"].append(msg["token"])
                    rec["t"].append(now)
                elif msg.get("done"):
                    rec["done"] = msg
                    if "error" in msg:
                        rec["error"] = msg["error"]
        rec["ended"] = time.monotonic()
    except asyncio.CancelledError:
        rec["cut"] = True
        raise
    except (OSError, ValueError, asyncio.IncompleteReadError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        if writer is not None:
            writer.close()


def new_record(req, due):
    return {"id": req["id"], "due": due, "sent": None, "status": None,
            "tokens": [], "t": [], "done": None, "error": None,
            "ended": None, "cut": False}


async def run(args):
    u = urlparse(args.url)
    with open(args.schedule) as f:
        reqs = json.load(f)
    start = args.start_at or time.monotonic() + 0.2
    end = start + args.seconds
    records, tasks = [], []

    async def sleep_until(t):
        d = t - time.monotonic()
        if d > 0:
            await asyncio.sleep(d)

    if args.mode == "open":
        async def fire(req):
            due = start + req["due_s"]
            await sleep_until(due)
            rec = new_record(req, due)
            records.append(rec)
            await one_request(u.hostname, u.port, req, rec)
        tasks = [asyncio.ensure_future(fire(r)) for r in reqs]
    else:
        it = iter(reqs)

        async def client():
            await sleep_until(start)
            for req in it:
                if args.mode == "closed" and time.monotonic() >= end:
                    return
                rec = new_record(req, time.monotonic())
                records.append(rec)
                await one_request(u.hostname, u.port, req, rec)
        n = args.clients if args.mode == "closed" else 1
        tasks = [asyncio.ensure_future(client()) for _ in range(n)]
    if args.mode == "serial":
        await asyncio.gather(*tasks)
    else:
        await sleep_until(end + args.drain)
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
    with open(args.out, "w") as f:
        json.dump({"start": start, "end": end, "records": records}, f)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--url", required=True)
    ap.add_argument("--schedule", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--mode", choices=("open", "closed", "serial"),
                    required=True)
    ap.add_argument("--start-at", type=float, default=0.0)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--clients", type=int, default=1)
    ap.add_argument("--drain", type=float, default=0.0)
    asyncio.run(run(ap.parse_args(argv)))


if __name__ == "__main__":
    main()
