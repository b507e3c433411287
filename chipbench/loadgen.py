"""Open-loop load generator: a process of its own, standard library only.

    python3 chipbench/loadgen.py <schedule.json> <results.json>

It never imports jax or the program, so it cannot hold the chip and does
not share the server's interpreter.  The schedule gives the port, the
route, a start time on the system-wide monotonic clock and, for every
request, when it is due after that start and its JSON body.  A scheduler
thread hands each request to a pool of sender threads at its due time,
whether or not earlier ones have been answered: a slow server gets no less
load.  Each request is timed from when it was DUE to its last byte, so a
stall's wait counts; how late the generator itself ran (send - due) is
reported beside it.
"""

import http.client
import json
import os
import queue
import sys
import threading
import time


def sender(port: int, route: str, timeout_s: float, work: queue.Queue, t0: float, out: list) -> None:
    conn = None
    while True:
        item = work.get()
        if item is None:
            break
        index, due_s, body, keep = item
        sent = time.monotonic() - t0
        status, answer = 0, None
        try:
            if conn is None:
                conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout_s)
            conn.request("POST", route, body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            data = resp.read()
            status = resp.status
            if keep and status == 200:
                answer = json.loads(data)
        except (OSError, http.client.HTTPException, ValueError):
            if conn is not None:
                conn.close()
            conn = None
        done = time.monotonic() - t0
        out[index] = {"due_s": due_s, "sent_s": sent, "done_s": done,
                      "status": status, "answer": answer}
    if conn is not None:
        conn.close()


def main(argv: list) -> int:
    with open(argv[1]) as f:
        schedule = json.load(f)
    requests = schedule["requests"]
    t0 = float(schedule["start_monotonic"])
    out = [None] * len(requests)
    work: queue.Queue = queue.Queue()
    threads = [
        threading.Thread(
            target=sender, daemon=True,
            args=(schedule["port"], schedule["route"], schedule["timeout_s"], work, t0, out),
        )
        for _ in range(int(schedule["threads"]))
    ]
    for t in threads:
        t.start()
    for index, req in enumerate(requests):
        wait = t0 + req["due_s"] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        work.put((index, req["due_s"], json.dumps(req["body"]).encode(), req.get("keep", False)))
    for _ in threads:
        work.put(None)
    deadline = time.monotonic() + float(schedule["timeout_s"]) + 60.0
    for t in threads:
        t.join(timeout=max(0.0, deadline - time.monotonic()))
    with open(argv[2] + ".tmp", "w") as f:
        json.dump({"results": out, "imports_jax": "jax" in sys.modules}, f)
    os.replace(argv[2] + ".tmp", argv[2])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
