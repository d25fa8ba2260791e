"""Open-loop HTTP load client of the gateway cells. Runs in a process of
its own and never imports JAX, so the chip stays with the server.

    python bench/client.py --traffic JSON --seed N --seconds S --url URL

It builds the run's arrival schedule (``mix.arrivals``) from the traffic
object the server side read (``--traffic``, its JSON text), reads the
window's start (a ``time.monotonic()`` value, shared by every process on
the machine) from its standard input, and POSTs each campaign at its due
time from a thread of its own, whether or not earlier ones were answered.
Prints one JSON line per arrival: due, sent and answered times, the HTTP
status and the campaign id.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import mix  # noqa: E402


def post(url, token, body, timeout):
    req = urllib.request.Request(
        url + "/campaigns", data=json.dumps(body).encode(), method="POST",
        headers={"Authorization": f"Bearer {token}",
                 "Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read()).get("id")
    except urllib.error.HTTPError as e:
        return e.code, None
    except OSError:
        return 0, None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--url", required=True)
    args = ap.parse_args(argv)
    traffic = json.loads(args.traffic)
    schedule = mix.arrivals(traffic, args.seed, args.seconds)
    timeout = float(traffic["arrivals"]["submit_timeout_s"])
    print(json.dumps({"ready": len(schedule)}), flush=True)
    t0 = float(sys.stdin.readline())
    out, threads = [None] * len(schedule), []

    def send(i, a):
        sent = time.monotonic()
        status, cid = post(args.url, f"tok-{a['tenant']}", a["body"],
                           timeout)
        out[i] = {"index": a["index"], "tenant": a["tenant"],
                  "due": t0 + a["due_s"], "sent": sent,
                  "answered": time.monotonic(), "status": status,
                  "id": cid}

    for i, a in enumerate(schedule):
        delay = t0 + a["due_s"] - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        th = threading.Thread(target=send, args=(i, a))
        th.start()
        threads.append(th)
    for th in threads:
        th.join()
    for rec in out:
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
