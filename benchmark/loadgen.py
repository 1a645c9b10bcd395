"""Open-loop HTTP load generator. Runs as a child process and never imports
JAX (the parent holds the chip).

    python3 benchmark/loadgen.py <plan.json> <out.json>

The plan holds the server's port, the instant (``time.monotonic()``, one
clock for every process of a machine) at which the schedule starts, and the
requests: each with its due offset, its prompt tokens and its answer length.
Every request is sent when it is due, whatever the server is doing, on a
connection of its own, to ``/generate`` with ``"stream": true``; the time of
each streamed token is taken as its line arrives. One thread, asyncio.
"""

from __future__ import annotations

import asyncio
import json
import sys
import time


async def one_request(port: int, t_start: float, req: dict) -> dict:
    due = t_start + req["due_s"]
    delay = due - time.monotonic()
    if delay > 0:
        await asyncio.sleep(delay)
    out = {"id": req["id"], "due": due, "sent": time.monotonic(), "status": 0,
           "token_times": [], "tokens": [], "final": None, "error": None}
    body = json.dumps({"tokens": req["prompt"], "max_new_tokens": req["max_new"],
                       "temperature": 0.0, "eos_id": -1, "stream": True}).encode()
    try:
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(b"POST /generate HTTP/1.1\r\nHost: 127.0.0.1\r\n"
                     b"Content-Type: application/json\r\nConnection: close\r\n"
                     + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
        await writer.drain()
        status = await reader.readline()
        out["status"] = int(status.split()[1])
        while (await reader.readline()).strip():
            pass  # headers
        while True:
            line = await reader.readline()
            if not line:
                break
            if not line.startswith(b"{"):
                continue  # chunk framing
            now = time.monotonic()
            msg = json.loads(line)
            if "token" in msg:
                out["token_times"].append(now)
                out["tokens"].append(msg["token"])
            else:
                out["final"] = msg
                if msg.get("error"):
                    out["error"] = msg["error"]
                if msg.get("done") or out["status"] != 200:
                    break
        writer.close()
    except (OSError, ValueError, IndexError, asyncio.IncompleteReadError) as e:
        out["error"] = f"{type(e).__name__}: {e}"
    out["done"] = time.monotonic()
    return out


async def drive(plan: dict) -> list[dict]:
    tasks = [asyncio.create_task(one_request(plan["port"], plan["t_start"], r))
             for r in plan["requests"]]
    return list(await asyncio.gather(*tasks))


def main(argv: list[str]) -> int:
    plan_path, out_path = argv
    with open(plan_path) as f:
        plan = json.load(f)
    results = asyncio.run(drive(plan))
    with open(out_path, "w") as f:
        json.dump(results, f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
