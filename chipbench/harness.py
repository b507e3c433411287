"""The harness behind chipbench/run.py: the session around a window, the
server under test, the ingest cell, and the result line."""

from __future__ import annotations

import argparse
import concurrent.futures
import faulthandler
import gc
import json
import os
import shutil
import socket
import sys
import threading
import time
import urllib.request


def _process_start_monotonic() -> float:
    """time.monotonic() at which this process was created (Linux), so that
    set-up counts the interpreter's own start and the imports."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        return time.monotonic() - max(age, 0.0)
    except (OSError, ValueError, IndexError):
        return time.monotonic()


T_PROCESS = _process_start_monotonic()

from chipbench import compare, costs, reference, spec, trace, traffic  # noqa: E402

STATUS_PORT = 20000  # the program's monitoring server: 20000 + process id
HARD_DEADLINE_S = 1150
WORK = os.path.join(spec.ROOT, ".chipbench")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    age = time.monotonic() - T_PROCESS
    print(f"[chipbench +{age:7.2f}s] {msg}", file=sys.stderr, flush=True)


class CompileCounter:
    """Counts jax's backend compilations (cache look-ups included) and
    their seconds, by phase: 'setup', 'window', 'after'."""

    def __init__(self) -> None:
        self.phase = "setup"
        self.count: dict = {}
        self.seconds: dict = {}
        self._lock = threading.Lock()

    def install(self) -> None:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            with self._lock:
                p = self.phase
                self.count[p] = self.count.get(p, 0) + 1
                self.seconds[p] = self.seconds.get(p, 0.0) + duration


def http_json(port: int, route: str, payload=None, timeout: float = 240.0):
    url = f"http://127.0.0.1:{port}{route}"
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"},
        method="GET" if payload is None else "POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def exit_when_the_server_thread_dies() -> None:
    default_hook = threading.excepthook

    def hook(args) -> None:
        default_hook(args)
        if args.thread is not None and args.thread.name == "pw-server":
            log("the server thread died; exiting")
            sys.stderr.flush()
            os._exit(1)

    threading.excepthook = hook


def dry_cut(cell: spec.Cell) -> spec.Cell:
    """The CPU rehearsal's sizes: the architecture's own cut of the model
    (never a width), a small store, small files."""
    import dataclasses

    config = json.loads(json.dumps(cell.config))
    config["model"] = cell.arch.costs.dry_cut(config["model"])
    config["store"]["reserved_space"] = 8192
    tr = json.loads(json.dumps(cell.traffic))
    tr["docs_per_file"] = 64
    tr["backlog_docs_per_s"] = {str(cell.chips): 192}  # a few files: the CPU is slow
    tr["sample_queries"] = 8
    tr["pool_docs"] = 24
    if tr["kind"] == "retrieve_open_loop":
        tr.update(store_docs=512, docs_per_file=64, rate_qps=20.0, generator_threads=16)
    return dataclasses.replace(cell, config=config, traffic=tr)


class Server:
    """The system under test, started as a deployment starts it."""

    def __init__(self, cell: spec.Cell, seed: int, docs_dir: str,
                 refresh_interval_s: float | None = None):
        import pathway_tpu as pw
        from pathway_tpu.stdlib.indexing.nearest_neighbors import BruteForceKnnFactory
        from pathway_tpu.xpacks.llm.document_store import DocumentStore
        from pathway_tpu.xpacks.llm.servers import DocumentStoreServer

        store = cell.config["store"]
        self.program = cell.arch.program
        table = pw.io.jsonlines.read(
            docs_dir, schema=pw.schema_from_types(data=str), mode="streaming",
            batch_per_file=True,
            refresh_interval=refresh_interval_s or cell.traffic["refresh_interval_s"],
        )
        factory = BruteForceKnnFactory(
            embedder=self.program.embedder(cell.config["model"], store, seed),
            reserved_space=store["reserved_space"],
        )
        self.port = free_port()
        self.k = int(store["k"])
        server = DocumentStoreServer(
            "127.0.0.1", self.port, DocumentStore(table, retriever_factory=factory)
        )
        run_kwargs = {"mesh": f"dp={cell.chips}"} if cell.chips > 1 else {}
        self.thread = server.run(threaded=True, with_http_server=True, **run_kwargs)

    def rows(self) -> int:
        """Documents the ingest pipeline has dispatched to the device: the
        program's own counter (/status "device_pipeline"."rows"), read in
        process because the window polls it."""
        from pathway_tpu.internals.device_pipeline import pipeline_status

        return int(pipeline_status().get("rows", 0))

    def wait_rows(self, want: int, deadline_s: float) -> None:
        deadline = time.monotonic() + deadline_s
        while self.rows() < want:
            if not self.thread.is_alive():
                raise RuntimeError("the server thread died")
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"{self.rows()} of {want} documents after {deadline_s:.0f}s"
                )
            time.sleep(0.02)

    def wait_ready(self, deadline_s: float = 60.0) -> None:
        """Until the REST endpoint answers (it comes up beside the engine)."""
        deadline = time.monotonic() + deadline_s
        while True:
            try:
                http_json(self.port, "/v1/statistics", {}, timeout=30.0)
                return
            except OSError:
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.1)

    def retrieve(self, text: str):
        return http_json(
            self.port, "/v1/retrieve",
            {"query": text, "k": self.k, "metadata_filter": None,
             "filepath_globpattern": None},
        )

    def retrieve_round(self, texts: list) -> list:
        """`texts` asked at once (one serving micro-batch); a failed
        request's answer is None."""
        def ask(text):
            try:
                return self.retrieve(text)
            except (OSError, ValueError) as exc:
                log(f"retrieve failed: {exc!r}")
                return None

        with concurrent.futures.ThreadPoolExecutor(len(texts)) as pool:
            return list(pool.map(ask, texts))

    def status(self) -> dict:
        return http_json(STATUS_PORT, "/status", timeout=60.0)

    def stop(self) -> None:
        import pathway_tpu as pw
        from pathway_tpu.internals.runner import last_engine

        last_engine().terminate_flag.set()
        self.thread.join(timeout=60)
        if self.thread.is_alive():
            raise RuntimeError("the server did not stop")
        pw.G.clear()
        self.program.release()
        gc.collect()


class Marker:
    """A tiny program on each chip.  Programs run on a chip in the order
    they were enqueued, so when a marker enqueued now is done, everything
    the server had dispatched before is done too; and its events cut the
    window on the device's clock in a traced run."""

    def __init__(self, chips: int):
        import jax
        import jax.numpy as jnp

        def chipbench_marker(x):
            return x + 1

        self._fn = jax.jit(chipbench_marker)
        self._xs = [
            jax.device_put(jnp.zeros((8, 128), jnp.float32), d)
            for d in jax.local_devices()[:chips]
        ]
        self.sync()  # compile

    def sync(self) -> float:
        import jax

        jax.block_until_ready([self._fn(x) for x in self._xs])
        return time.perf_counter()


def write_warmup(corpus, tr: dict, docs_dir: str) -> int:
    """The warm-up files, straight into the watched directory."""
    n_warm = int(tr["warmup_files"])
    for i in range(n_warm):
        corpus.write_file(i, os.path.join(docs_dir, f"a_warm_{i:05d}.jsonl"))
    return n_warm


def write_backlog(corpus, cell, seconds: float, first: int, staging: str, out: dict) -> None:
    """The backlog, into the watched directory's sibling: `backlog_docs_per_s`
    x --seconds documents, in whole files whose names sort in ingest order."""
    docs = float(cell.traffic["backlog_docs_per_s"][str(cell.chips)]) * seconds
    n_files = max(2, -(-int(docs) // corpus.docs_per_file))
    for i in range(n_files):
        corpus.write_file(first + i, os.path.join(staging, f"b_backlog_{i:05d}.jsonl"))
    out["backlog_files"] = n_files


def device_facts(chips: int, dry: bool) -> dict:
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if dry:
        if platform != "cpu":
            raise SystemExit("--dry is the CPU rehearsal: set JAX_PLATFORMS=cpu")
    elif platform != "tpu":
        log(f"no accelerator: jax reports platform {platform!r}")
        raise SystemExit(3)
    if len(devices) < chips:
        log(f"the cell needs {chips} chip(s), jax reports {len(devices)}")
        raise SystemExit(3)
    return {"platform": platform, "kind": devices[0].device_kind, "count": len(devices)}


def memory_peak(chips: int):
    import jax

    peaks = []
    for d in jax.local_devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def pick_sample(corpus, first_file: int, n_docs: int, tr: dict, seed: int):
    """A seeded sample of the window's documents: `sample_queries` of the
    longest class (one length, so the read-back compiles one program), and
    `pool_docs` others of any length.  Only whole files count."""
    import numpy as np

    rng = np.random.default_rng([int(seed), 2])
    whole_files = n_docs // corpus.docs_per_file
    if whole_files < 1:
        raise RuntimeError(
            f"the window ingested {n_docs} documents, less than one file"
        )
    longest = [
        (f, int(p)) for f in range(whole_files) for p in corpus.longest_positions()
    ]
    picks = rng.permutation(len(longest))[: int(tr["sample_queries"])]
    sample = [longest[i] for i in picks]
    pool = [
        divmod(int(g), corpus.docs_per_file)
        for g in rng.integers(0, whole_files * corpus.docs_per_file,
                              size=int(tr["pool_docs"]))
    ]
    texts: dict = {}
    for f in sorted({f for f, _ in sample + pool}):
        texts[f] = corpus.file_docs(first_file + f)
    return [texts[f][p] for f, p in sample], [texts[f][p] for f, p in pool]


def probe_of(text: str, words: int) -> str:
    """A short query cut from a document: its first `words` words.  It lies
    well away from the long documents' embeddings, so an error of the
    encoder reaches its scores in first order."""
    return " ".join(text.split(" ")[:words])


def mixed_rounds(own: list, probes: list, round_size: int) -> list:
    """Rounds of `round_size` queries, half of them own texts of the
    longest class: a round's batch then always has the one padded shape."""
    half = round_size // 2
    rounds = []
    for lo in range(0, len(own), half):
        rounds.append(own[lo : lo + half] + probes[lo : lo + half])
    return rounds


class Session:
    """What every kind of cell needs around its window: the run's
    directory, the devices, the compile counter, the marker, the trace."""

    def __init__(self, cell: spec.Cell, args):
        self.cell, self.args = cell, args
        self.run_dir = os.path.join(WORK, "run", cell.name)
        self.docs_dir = os.path.join(self.run_dir, "docs")
        self.staging = os.path.join(self.run_dir, "staging")
        self.trace_dir = os.path.join(self.run_dir, "trace")
        shutil.rmtree(self.run_dir, ignore_errors=True)
        os.makedirs(self.docs_dir)
        os.makedirs(self.staging)
        os.environ["PATHWAY_NATIVE_CACHE"] = os.path.join(WORK, "native")
        os.environ.update(cell.config.get("env", {}))  # the deployment's settings
        self.tracing = bool(args.trace)
        self.compiles = CompileCounter()
        self.sampler = None
        self.samples: list = []
        self.status_open = self.status_close = None
        self.status_interval_s = None
        self._t_status_open = None

    def attach_device(self) -> None:
        """jax comes in here, after a cell has started its host-only work."""
        self.device = device_facts(self.cell.chips, self.args.dry)
        log(f"devices: {self.device}")
        from pathway_tpu.internals import compile_cache

        log(f"compile cache: {compile_cache.configure()}")
        self.compiles.install()
        self.peaks = None if self.args.dry else costs.peaks(self.device["kind"])
        self.marker = Marker(self.cell.chips)

    def open_window(self, server: "Server") -> float:
        """Status snapshot and profiler on (traced run), then the opening
        marker.  Returns perf_counter at the marker's end."""
        if self.tracing:
            import jax

            self.status_open = server.status()
            self._t_status_open = time.monotonic()
            self.sampler = trace.HostSampler()
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
            self.sampler.start()
        self.compiles.phase = "window"
        self.host_open = self.marker.sync()
        return self.host_open

    def close_window(self, server: "Server") -> None:
        """The closing marker: everything dispatched so far is done when it
        is."""
        self.marker.sync()
        self.compiles.phase = "after"
        self._t_closed = time.monotonic()

    def after_window(self, server: "Server") -> None:
        """Traced run: the second status snapshot, sampler and profiler off."""
        if self.tracing:
            import jax

            self.status_close = server.status()
            self.status_interval_s = time.monotonic() - self._t_status_open
            self.samples = self.sampler.stop()
            jax.profiler.stop_trace()

    def reduced_trace(self):
        if not self.tracing or self.args.dry:
            return None
        events = trace.load_xplane(self.trace_dir)
        if self.args.dump_trace:
            os.makedirs(os.path.dirname(os.path.abspath(self.args.dump_trace)), exist_ok=True)
            with open(self.args.dump_trace, "w") as f:
                json.dump({"events": events, "samples": self.samples,
                           "host_open_s": self.host_open}, f)
        return trace.reduce(events, samples=self.samples, host_open_s=self.host_open)


def read_controls(encoder, own, probes, pool, k: int) -> dict:
    """Readings for PERF.md: the reference in lower precisions put in the
    program's place.  `encoder_fp8.index_bf16` is the control: every
    precision the configuration states, one rung down."""
    control = {}
    for enc_kind, index_kind in (("int8", None), ("fp8", None), ("fp8", "bf16")):
        low = lambda texts, kind=enc_kind: encoder.embed(texts, lower_precision=kind)  # noqa: E731
        rounder = None if index_kind is None else (
            lambda v, kind=index_kind: reference.round_vectors(v, kind)
        )
        own_rows, probe_rows = compare.control_answers(
            own, probes, pool, low, k, index_round=rounder
        )
        control[f"encoder_{enc_kind}.index_{index_kind or 'f32'}"] = compare.compare(
            own, own_rows, probes, probe_rows, pool, encoder.embed, k
        )
    return control


def ingest_cell(session: Session) -> dict:
    """Traffic kind `ingest_backlog`: a backlog released at once."""
    cell, args = session.cell, session.args
    tr = cell.traffic
    corpus = traffic.Corpus(tr, args.seed)
    written = {"warm_files": write_warmup(corpus, tr, session.docs_dir)}
    writer = threading.Thread(
        target=write_backlog, name="chipbench-corpus",
        args=(corpus, cell, args.seconds, written["warm_files"], session.staging, written),
    )
    writer.start()  # numpy and file writes, while jax imports and set-up runs

    session.attach_device()
    server = Server(cell, args.seed, session.docs_dir)
    log("server started")
    warm_docs = written["warm_files"] * corpus.docs_per_file
    server.wait_rows(warm_docs, 1000.0)
    session.marker.sync()
    log(f"warm-up files ingested; compiles {session.compiles.count}")
    server.wait_ready()
    # the read-back's two programs: a mixed round and a round of probes
    warm_texts = corpus.file_docs(0)
    round_size = int(tr["query_round"])
    probe_words = int(tr["probe_words"])
    warm_own = [warm_texts[p] for p in corpus.longest_positions()][: round_size // 2]
    warm_probes = [probe_of(t, probe_words) for t in warm_texts[:round_size]]
    for warm_round in (warm_own + warm_probes[: round_size - len(warm_own)], warm_probes):
        if any(a is None for a in server.retrieve_round(warm_round)):
            raise RuntimeError("a warm-up query failed")
    log(f"warm-up queries answered; compiles {session.compiles.count} "
        f"{session.compiles.seconds}")
    writer.join()
    if "backlog_files" not in written:
        raise RuntimeError("writing the backlog failed")

    # -- the window -------------------------------------------------------------
    rows_open = server.rows()
    session.open_window(server)
    t_open = time.monotonic()
    setup_s = t_open - T_PROCESS
    for name in sorted(os.listdir(session.staging)):
        os.rename(os.path.join(session.staging, name), os.path.join(session.docs_dir, name))
    time.sleep(max(0.0, args.seconds - (time.monotonic() - t_open)))
    # close on a dispatch boundary, so the count is whole batches
    rows_close = server.rows()
    boundary_deadline = time.monotonic() + 3.0
    while time.monotonic() < boundary_deadline:
        now = server.rows()
        if now != rows_close:
            rows_close = now
            break
        time.sleep(0.002)
    session.close_window(server)
    window_s = time.monotonic() - t_open
    docs_in_window = rows_close - rows_open
    session.after_window(server)
    peak_bytes = memory_peak(cell.chips)
    log(f"window: {docs_in_window} documents in {window_s:.3f}s; "
        f"set-up {setup_s:.2f}s; compiles {session.compiles.count}")

    from pathway_tpu.internals.device_pipeline import pipeline_status

    if int(pipeline_status().get("fallbacks", 0)):
        raise RuntimeError("the ingest pipeline fell back to the synchronous path")

    # -- read back what the window ingested ---------------------------------------
    n_backlog_docs = written["backlog_files"] * corpus.docs_per_file
    ran_dry = docs_in_window >= n_backlog_docs
    if ran_dry:
        log("the backlog ran dry before the window closed: the rate is capped")
    queries, pool = pick_sample(
        corpus, written["warm_files"], min(docs_in_window, n_backlog_docs), tr,
        args.seed,
    )
    probes = [probe_of(q, probe_words) for q in queries]
    own_answers, probe_answers = [], []
    for round_ in mixed_rounds(queries, probes, round_size):
        got = server.retrieve_round(round_)
        own_answers += got[: len(round_) // 2]
        probe_answers += got[len(round_) // 2 :]
    server.stop()

    # -- compare with the plain reference -----------------------------------------
    t_ref = time.monotonic()
    store, model = cell.config["store"], cell.config["model"]
    encoder = cell.arch.reference.Encoder(model, args.seed, max_len=store["max_len"])
    numbers = compare.compare(
        queries, own_answers, probes, probe_answers, pool, encoder.embed, store["k"]
    )
    answers = own_answers + probe_answers
    control = None
    if args.control:
        returned = [r["text"] for rows in answers if rows for r in rows]
        control = read_controls(encoder, queries, probes, pool + returned, store["k"])
    encoder.free()

    tokens = [min(int(w) + 2, store["max_len"]) for w in corpus.lengths]
    return {
        "numbers": numbers, "control": control, "peak_bytes": peak_bytes,
        "attempted": int(docs_in_window + len(answers)),
        "failed": int(numbers["retrievable_missing"] + sum(a is None for a in answers)),
        "end_to_end": {"setup_s": setup_s, "ingest_docs_per_s": docs_in_window / window_s},
        "ctx": {
            "window_s": window_s, "docs_in_window": docs_in_window,
            "docs_per_file": corpus.docs_per_file, "tokens_per_file": tokens,
        },
        "facts": {
            "window_s": window_s, "docs_in_window": docs_in_window,
            "backlog_ran_dry": ran_dry, "reference_s": time.monotonic() - t_ref,
        },
    }


def emit(session: Session, outcome: dict) -> None:
    """The per-layer readers (traced run), then the result line."""
    cell, args = session.cell, session.args
    correct, compared = compare.verdict(outcome["numbers"], cell.config["limits"])
    units = spec.units()
    device_out = dict(session.device, memory_peak_bytes=outcome["peak_bytes"])
    result = {
        "correct": bool(correct),
        "attempted": outcome["attempted"],
        "failed": outcome["failed"],
    }
    if not session.tracing:
        metrics = {n: outcome["end_to_end"][n] for n in cell.end_to_end}
    else:
        reduced = session.reduced_trace()
        ctx = dict(
            outcome["ctx"], cell=cell, arch=cell.arch, device=session.device,
            peaks=session.peaks,
            trace=reduced, status_open=session.status_open,
            status_close=session.status_close,
            status_interval_s=session.status_interval_s,
            compiles_in_window=session.compiles.count.get("window", 0),
        )
        metrics = {}
        for m in cell.per_layer:
            value = m.read(ctx)
            if value is not None:
                metrics[m.name] = value
        if reduced is not None:
            device_out["busy_s"] = reduced["busy_s"]
            device_out["window_s"] = reduced["window_s"]
            result["breakdown"] = {
                "device_ops": trace.top(reduced["ops"]),
                "idle_gaps": trace.top(reduced["idle_gaps"]),
            }
    result["metrics"] = {n: {"value": v, "unit": units[n]} for n, v in metrics.items()}
    result["device"] = device_out
    result["facts"] = dict(
        outcome["facts"], compiles=session.compiles.count,
        compile_s=session.compiles.seconds, dry=args.dry,
    )
    if outcome.get("control") is not None:
        result["control"] = outcome["control"]
    result["compared"] = compared  # last: each number beside its limit
    shutil.rmtree(session.run_dir, ignore_errors=True)
    for name, c in compared.items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="chipbench.run")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--dry", action="store_true",
                        help="CPU rehearsal at tiny sizes (JAX_PLATFORMS=cpu)")
    parser.add_argument("--control", action="store_true",
                        help="also read the lower-precision controls' numbers")
    parser.add_argument("--sweep", default=None,
                        help="retrieve cells: rates (qps, comma-separated) to try "
                             "for --seconds each after one set-up; prints a table")
    parser.add_argument("--dump-trace", default=None,
                        help="write the traced run's events and samples here (json)")
    args = parser.parse_args(argv)
    faulthandler.dump_traceback_later(HARD_DEADLINE_S, exit=True)
    exit_when_the_server_thread_dies()

    cell = spec.cell(args.workload)
    if args.dry:
        cell = dry_cut(cell)
    from chipbench import retrieve

    kinds = {"ingest_backlog": ingest_cell, "retrieve_open_loop": retrieve.retrieve_cell}
    kind = cell.traffic["kind"]
    if kind not in kinds:
        raise SystemExit(f"traffic kind {kind!r} has no generator here")
    session = Session(cell, args)
    outcome = kinds[kind](session)
    if "sweep" in outcome:
        print(json.dumps(outcome), flush=True)
    else:
        emit(session, outcome)
    faulthandler.cancel_dump_traceback_later()
    return 0


