"""The load generator: a child process of the benchmark that never imports
jax (standard library only), so that it shares neither the chip nor the
engine loop's interpreter lock.

It reads one JSON plan on standard input (``chipbench/traffic.py`` made
it), sends the requests to ``POST /generate`` with ``"stream": true``,
stamps every token with the system-wide monotonic clock, and writes one
JSON object of records on standard output when the last measured request
has answered or the drain time is over. An open loop sends each request
when it is due, whatever the server does; a closed loop gives each client
its next request when the last one has answered.
"""

import http.client
import json
import sys
import threading
import time


def send(addr, req, rec):
    """One request, streamed; fills ``rec`` in place."""
    host, port = addr.rsplit(":", 1)
    payload = json.dumps(req["body"]).encode()
    conn = http.client.HTTPConnection(host, int(port), timeout=300)
    rec["sent"] = time.monotonic()
    try:
        conn.request(
            "POST", "/generate", body=payload,
            headers={"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        rec["status"] = resp.status
        if resp.status != 200:
            rec["error"] = resp.read(2000).decode("utf-8", "replace")
            return
        while True:
            line = resp.readline()
            if not line:
                break
            now = time.monotonic()
            item = json.loads(line)
            if "t" in item:
                rec["tokens"].append(item["t"])
                rec["times"].append(now)
            elif item.get("done"):
                rec["done"] = now
                rec["timing"] = item.get("timing", {})
            else:
                rec["error"] = json.dumps(item)[:2000]
    except (OSError, ValueError, http.client.HTTPException) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        rec["end"] = time.monotonic()
        conn.close()


def new_record(req, due):
    return {
        "id": req["id"], "due": due, "sent": None, "status": None,
        "tokens": [], "times": [], "done": None, "timing": {},
        "error": None, "end": None,
        "prompt_len": len(req["body"]["prompt"]),
        "max_new_tokens": req["body"]["max_new_tokens"],
    }


def open_loop(plan, addr, t0):
    records, threads = [], []
    for req in sorted(plan["requests"], key=lambda r: r["due_s"]):
        due = t0 + req["due_s"]
        delay = due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        rec = new_record(req, due)
        records.append(rec)
        th = threading.Thread(target=send, args=(addr, req, rec), daemon=True)
        th.start()
        threads.append(th)
    return records, threads


def closed_loop(plan, addr, t0):
    records, lock = [], threading.Lock()
    stop_at = t0 + plan["ramp_s"] + plan["seconds"]
    n = plan["clients"]

    def client(c):
        mine = plan["requests"][c::n]
        i = 0
        while time.monotonic() < stop_at:
            req = mine[i % len(mine)]
            i += 1
            rec = new_record(req, time.monotonic())
            with lock:
                records.append(rec)
            send(addr, req, rec)
            if rec["error"] is not None or rec["status"] != 200:
                time.sleep(0.05)  # a refusing server is not hammered

    delay = t0 - time.monotonic()
    if delay > 0:
        time.sleep(delay)
    threads = [
        threading.Thread(target=client, args=(c,), daemon=True)
        for c in range(n)
    ]
    for th in threads:
        th.start()
    return records, threads


def main():
    plan = json.load(sys.stdin)
    addr, t0 = plan["address"], plan["t0"]
    loop = open_loop if plan["loop"] == "open" else closed_loop
    records, threads = loop(plan, addr, t0)
    give_up = t0 + plan["ramp_s"] + plan["seconds"] + plan["drain_s"]
    for th in threads:
        th.join(max(0.0, give_up - time.monotonic()))
    json.dump({"t0": t0, "records": list(records)}, sys.stdout)
    sys.stdout.flush()


if __name__ == "__main__":
    main()
