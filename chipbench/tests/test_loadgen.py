"""The load generator and its schedule: reproducible from the seed, the
same arrivals and lengths for every seed in another order, open loop, and
a process that imports no jax."""

import http.server
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

from chipbench import retrieve, spec

ROOT = spec.ROOT
MIX = {"query_words": [6, 24], "seeded_words": 2, "sample_queries": 4}
DOCS = [" ".join(f"w{d}x{i}" for i in range(30)) for d in range(20)]
VOCAB = np.array([f"v{i}" for i in range(50)], dtype=object)


def _requests(seed, seconds=10.0, rate=20.0):
    return retrieve.make_requests(MIX, DOCS, VOCAB, seed, seconds, rate, 6)


def test_schedule_is_reproducible_and_the_same_multiset_for_every_seed():
    a, b, c = _requests(7), _requests(7), _requests(2**31 + 99)
    assert a == b and a != c
    assert len(a) == len(c) == 200

    def gaps(reqs):  # the n gaps add up to the window; the first one is the tail
        due = [r["due_s"] for r in reqs]
        return sorted(np.round(list(np.diff(due)) + [10.0 - due[-1]], 9))

    assert gaps(a) == gaps(c)  # the same arrivals, in another order
    words = lambda reqs: sorted(len(r["body"]["query"].split(" ")) for r in reqs)  # noqa: E731
    assert words(a) == words(c) and words(a)[0] == 8 and words(a)[-1] == 26
    assert a[0]["due_s"] == 0.0 and 9.0 < a[-1]["due_s"] < 10.0
    mean_gap = np.mean(np.diff([r["due_s"] for r in a]))
    assert abs(mean_gap - 1 / 20.0) < 0.005
    assert len({r["body"]["query"] for r in a}) == 200  # distinct: no cache hit
    kept = [r for r in a if r["keep"]]
    assert 4 <= len(kept) <= 5
    assert max(len(r["body"]["query"].split(" ")) for r in kept) == 26  # the longest


def test_query_words_come_from_the_source_passage():
    for r in _requests(3)[:20]:
        words = r["body"]["query"].split(" ")
        assert " ".join(words[:-2]) in DOCS[r["source"]]
        assert all(w in VOCAB for w in words[-2:])


class _Slow(http.server.BaseHTTPRequestHandler):
    def do_POST(self):
        self.rfile.read(int(self.headers["Content-Length"]))
        time.sleep(0.3)
        body = json.dumps([{"text": "t", "score": 1.0}]).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *args):
        pass


def test_generator_is_open_loop_times_from_due_and_imports_no_jax(tmp_path):
    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Slow)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        n = 12
        schedule = {
            "port": server.server_address[1], "route": "/v1/retrieve",
            "start_monotonic": time.monotonic() + 1.0, "timeout_s": 5.0, "threads": 2,
            "requests": [{"due_s": 0.05 * i, "keep": i == 0, "body": {"query": "q"}}
                         for i in range(n)],
        }
        (tmp_path / "s.json").write_text(json.dumps(schedule))
        proc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "chipbench", "loadgen.py"),
             str(tmp_path / "s.json"), str(tmp_path / "r.json")],
            timeout=60, env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
        )
        assert proc.returncode == 0
        out = json.loads((tmp_path / "r.json").read_text())
    finally:
        server.shutdown()
    assert out["imports_jax"] is False
    results = out["results"]
    assert all(r["status"] == 200 for r in results)
    assert results[0]["answer"] == [{"text": "t", "score": 1.0}] and results[1]["answer"] is None
    # two senders against a 0.3 s server: the backlog grows, and the wait is
    # counted because each request is timed from when it was due
    lat, failed = retrieve.latencies_ms(results, 5.0)
    assert failed == 0 and lat[0] < 450 and lat[-1] > 1000
    late = [r["sent_s"] - r["due_s"] for r in results]
    assert late[0] < 0.05 and late[-1] > 0.8
    assert retrieve.latencies_ms([None, results[0]], 5.0)[1] == 1


def test_loadgen_source_imports_only_the_standard_library():
    import ast

    with open(os.path.join(ROOT, "chipbench", "loadgen.py")) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add(node.module.split(".")[0])
    assert names <= {"http", "json", "queue", "sys", "threading", "time", "os"}
