#!/usr/bin/env python3
"""The load generator: a process of its own that imports neither JAX nor
the program. It speaks HTTP/1.1 over loopback with ``X-Remote-User``,
one kept-alive connection per client, as a Kubernetes client does.

    client.py --port P --plan plan.jsonl --out records.jsonl \
        --clients N --seconds S

Closed loop: each of the N clients takes the plan's next request when its
last one answered, until S seconds have passed; requests sent inside the
window are waited for after it closes (30 s at most; none answered by
then counts as failed). Bodies are kept as bytes during the window and
parsed only after it. The last stdout line is one JSON object: window
start (epoch), seconds, requests sent, and the generator's own CPU
seconds inside the window. A plan that runs out ends the run early and says
so (``plan_exhausted``); the warm-up is such a run.
"""

import argparse
import asyncio
import json
import time

ANSWER_S = 30.0  # a request with no answer by then has failed


async def connect(port: int):
    return await asyncio.open_connection("127.0.0.1", port)


async def exchange(reader, writer, port: int, req: dict):
    """-> (status, body bytes) over a kept-alive connection."""
    head = (f"{req['method']} {req['path']} HTTP/1.1\r\n"
            f"Host: 127.0.0.1:{port}\r\n"
            f"X-Remote-User: {req['user']}\r\n"
            "Accept: application/json\r\n\r\n")
    writer.write(head.encode())
    await writer.drain()
    line = await reader.readline()
    if not line:
        raise ConnectionResetError("closed before a status line")
    status = int(line.split(b" ")[1])
    length = 0
    while True:
        line = await reader.readline()
        if line in (b"\r\n", b"\n", b""):
            break
        k, _, v = line.partition(b":")
        if k.strip().lower() == b"content-length":
            length = int(v)
    body = await reader.readexactly(length) if length else b""
    return status, body


def names_of(body: bytes) -> list:
    """The objects a response names, as engine ids: ``namespace/name`` or
    ``name``; a list gives its items', a single object its own, an error
    Status none."""
    try:
        doc = json.loads(body)
    except ValueError:
        return ["<unparsable body>"]
    if not isinstance(doc, dict) or doc.get("kind") == "Status":
        return []
    items = doc["items"] if "items" in doc else [doc]
    out = []
    for it in items:
        meta = it.get("metadata") or {}
        ns, name = meta.get("namespace"), meta.get("name", "")
        out.append(f"{ns}/{name}" if ns else name)
    return out


async def drive(port: int, plan: list, clients: int, seconds: float) -> dict:
    conns = [await connect(port) for _ in range(clients)]
    records = []
    cursor = 0
    t_cpu0 = time.process_time()
    epoch0 = time.time()
    t0 = time.perf_counter()
    deadline = t0 + seconds
    cpu_in_window = None

    async def one_client(k: int):
        nonlocal cursor, cpu_in_window
        reader, writer = conns[k]
        while True:
            start = time.perf_counter()
            if start >= deadline or cursor >= len(plan):
                if cpu_in_window is None:
                    cpu_in_window = time.process_time() - t_cpu0
                return
            i = cursor
            cursor += 1
            rec = {"i": i, "client": k, "start": start - t0}
            try:
                status, body = await asyncio.wait_for(
                    exchange(reader, writer, port, plan[i]), ANSWER_S)
                rec.update(end=time.perf_counter() - t0, status=status,
                           body=body)
            except (asyncio.TimeoutError, OSError, ValueError,
                    asyncio.IncompleteReadError) as e:
                rec.update(end=time.perf_counter() - t0, status=0,
                           body=b"", error=f"{type(e).__name__}: {e}")
                writer.close()
                try:
                    reader, writer = conns[k] = await connect(port)
                except OSError:
                    records.append(rec)
                    return
            records.append(rec)

    await asyncio.gather(*(one_client(k) for k in range(clients)))
    closed = time.perf_counter() - t0
    for _, writer in conns:
        writer.close()
    for rec in records:
        rec["names"] = names_of(rec.pop("body")) if rec["status"] else []
    records.sort(key=lambda r: r["i"])
    return {"records": records,
            "summary": {"window_start_epoch": epoch0, "seconds": seconds,
                        "sent": len(records), "closed_after_s": closed,
                        "plan_exhausted": cursor >= len(plan),
                        "client_cpu_s": cpu_in_window or 0.0}}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--plan", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--clients", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    with open(args.plan) as f:
        plan = [json.loads(line) for line in f]
    out = asyncio.run(drive(args.port, plan, args.clients, args.seconds))
    with open(args.out, "w") as f:
        for rec in out["records"]:
            f.write(json.dumps(rec) + "\n")
    print(json.dumps(out["summary"]), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
