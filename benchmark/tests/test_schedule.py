"""The open-loop schedule is a pure function of the seed, offers a
fixed amount of work, and the generator measures from the due time."""

import http.server
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

from benchmark.harness import schedule
from benchmark.harness.cell import load_cell

HERE = os.path.dirname(os.path.abspath(__file__))
LOADGEN = os.path.join(os.path.dirname(HERE), "harness", "loadgen.py")


def _mix():
    return load_cell("rec-msd.serve-steady").traffic


def test_same_seed_same_schedule_other_seed_other_schedule():
    a = schedule.build_schedule(_mix(), 1000, 500, 7, 2.0, rate_qps=200)
    b = schedule.build_schedule(_mix(), 1000, 500, 7, 2.0, rate_qps=200)
    c = schedule.build_schedule(_mix(), 1000, 500, 8, 2.0, rate_qps=200)
    assert np.array_equal(a["due"], b["due"]) and a["bodies"] == b["bodies"]
    assert not np.array_equal(a["due"], c["due"])
    assert a["bodies"] != c["bodies"]


def test_fixed_work_sorted_arrivals_and_mix_shares():
    mix = _mix()
    s = schedule.build_schedule(mix, 5000, 800, 3, 10.0, rate_qps=400)
    assert s["n_window"] == 4000 and s["n_ramp"] == 800
    due = s["due"]
    assert (np.diff(due) >= 0).all()
    assert (due >= 0).sum() == 4000 and due.min() >= -mix["ramp_s"]
    qs = [json.loads(b) for b in s["bodies"]]
    item_share = np.mean(["items" in q for q in qs])
    assert abs(item_share - mix["item_query_share"]) < 0.02
    black = np.mean(["blacklist" in q for q in qs if "user" in q])
    assert abs(black - mix["blacklist_share"]) < 0.02
    nums = np.asarray([q["num"] for q in qs])
    assert abs((nums == 10).mean() - 0.8) < 0.03
    assert all(1 <= len(q["items"]) <= 3 for q in qs if "items" in q)
    assert all(1 <= len(q["blacklist"]) <= 5 for q in qs
               if "blacklist" in q)
    # the activity law: low user indices (the active ones) ask most
    users = np.asarray([int(q["user"][1:]) for q in qs if "user" in q])
    assert (users < 500).mean() > 0.2


def test_shares_partition_the_schedule(tmp_path):
    s = schedule.build_schedule(_mix(), 100, 50, 1, 1.0, rate_qps=100)
    seen = []
    for k in range(3):
        p = str(tmp_path / f"s{k}.npz")
        schedule.save_share(p, s, k, 3)
        seen.extend(np.load(p)["index"].tolist())
    assert sorted(seen) == list(range(len(s["due"])))


class _Slow(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    delay = 0.05

    def do_POST(self):
        n = int(self.headers.get("Content-Length") or 0)
        q = json.loads(self.rfile.read(n))
        time.sleep(self.delay)
        body = json.dumps({"itemScores": [
            {"item": "i1", "score": 1.0}] * min(2, q["num"])}).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):
        pass


def _offer(tmp_path, pool: int):
    """100 requests in one second to a server that takes 50 ms an
    answer, through a pool of ``pool`` connections; returns the
    generator's arrays and the client ports the server saw."""
    ports = set()

    class Handler(_Slow):
        def do_POST(self):
            ports.add(self.client_address[1])
            super().do_POST()

    httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    httpd.daemon_threads = True
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        mix = dict(_mix(), ramp_s=0.0)
        s = schedule.build_schedule(mix, 100, 50, 1, 1.0, rate_qps=100)
        sp, op = str(tmp_path / "s.npz"), str(tmp_path / "o.npz")
        schedule.save_share(sp, s, 0, 1)
        epoch = time.time() + 1.0
        rc = subprocess.run(
            [sys.executable, LOADGEN, "127.0.0.1",
             str(httpd.server_address[1]), sp, op, repr(epoch), "5.0",
             str(pool)],
            timeout=60).returncode
        assert rc == 0
        return np.load(op), ports
    finally:
        httpd.shutdown()
        httpd.server_close()
        t.join(timeout=5)
        assert not t.is_alive()


def test_generator_is_open_loop_and_times_from_due(tmp_path):
    """With a pool that covers the 5 requests outstanding at once, an
    open loop sends all on schedule (lateness of the send stays small,
    the server's delay does not hold the next request back) and every
    latency is at least the 50 ms."""
    z, _ = _offer(tmp_path, 16)
    assert z["ok"].all() and (z["status"] == 200).all()
    late = z["sent"] - z["due"]
    lat = z["done"] - z["due"]
    assert np.median(late) < 0.005 and late.max() < 0.05
    assert lat.min() >= 0.05
    # closed-loop on one connection would need 100 x 50 ms = 5 s
    assert z["done"].max() < 2.0


def test_generator_never_outgrows_its_pool(tmp_path):
    """Four connections serve at most 80 requests a second of the 100
    offered: the rest wait in the client (none fails, none opens a
    fifth connection), the wait shows as lateness of the send, and the
    latency still runs from the due time."""
    z, ports = _offer(tmp_path, 4)
    assert z["ok"].all() and len(ports) == 4
    late = z["sent"] - z["due"]
    lat = z["done"] - z["due"]
    assert late.max() > 0.2
    assert (lat >= late + 0.05 - 1e-3).all()
    assert 1.25 < z["done"].max() < 4.0


def test_generator_never_imports_jax():
    src = open(LOADGEN).read()
    assert "import jax" not in src and "predictionio_tpu" not in src
