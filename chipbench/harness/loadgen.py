"""Open-loop load generator: a child process that never imports JAX.

    python loadgen.py <host> <port> <path> <schedule.json> <t0>

Reads a schedule (``traffic.make_schedule``) and POSTs each request when
it is due, ``t0 + due_s`` on ``time.monotonic()``, which a parent on the
same machine shares.  One submitter (the main thread) and one short-lived
waiter thread per request in flight, as ``benchmark/decode_bench.py``'s
``run_open`` had it; unlike there a request is timed from when it was DUE,
not from when it was sent, and how late each was sent is reported.

Prints one JSON line per answered request as it completes:
``{"i", "due", "sent", "done", "status", "n_tokens", "head"}`` (times on
``time.monotonic()``, ``head`` the first 8 tokens), and ``{"end": true}``
when every request has been answered.
"""
from __future__ import annotations

import http.client
import json
import sys
import threading
import time

HEAD = 8
_print_lock = threading.Lock()


def _emit(obj) -> None:
    with _print_lock:
        sys.stdout.write(json.dumps(obj) + "\n")
        sys.stdout.flush()


def _one(host, port, path, i, body, due) -> None:
    status, tokens = -1, []
    sent = time.monotonic()
    try:
        conn = http.client.HTTPConnection(host, port, timeout=300)
        try:
            conn.request("POST", path, body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            payload = resp.read()
            status = resp.status
        finally:
            conn.close()
        if status == 200:
            tokens = json.loads(payload).get("tokens", [])
    except (OSError, http.client.HTTPException, ValueError) as e:
        print(f"loadgen: request {i}: {type(e).__name__}: {e}",
              file=sys.stderr)
    _emit({"i": i, "due": due, "sent": sent, "done": time.monotonic(),
           "status": status, "n_tokens": len(tokens),
           "head": tokens[:HEAD]})


def main(argv) -> int:
    host, port, path, schedule_path, t0 = argv[1:6]
    port, t0 = int(port), float(t0)
    with open(schedule_path) as f:
        schedule = json.load(f)
    bodies = [json.dumps({"prompt": r["prompt"],
                          "max_new_tokens": r["max_new_tokens"]}).encode()
              for r in schedule]
    threads = []
    for r, body in zip(schedule, bodies):
        due = t0 + r["due_s"]
        wait = due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        t = threading.Thread(target=_one, daemon=True,
                             args=(host, port, path, r["i"], body, due))
        t.start()
        threads.append(t)
    for t in threads:
        t.join(600)
    _emit({"end": True})
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
