"""Run one benchmark cell once: an LM offload session through
``avec.connect`` -> TCP -> ``DestinationExecutor`` -> the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Two processes.  ``bench/server.py`` is the destination and alone touches
the chip.  This process is the edge side: pinned to the CPU, it opens one
``avec.connect(["tcp://127.0.0.1:<port>"], shadow_every=0)`` client over
loopback TCP, generates the cell's traffic from the seed
(``bench/traffic.py``), warms up exactly the prompt lengths the traffic
uses, and measures for ``--seconds``.  Each request is one ``prefill`` and
then greedy ``decode`` calls, each fed the argmax of the logits the client
received, until its drawn output length; the client sends the next request
when the last one has finished.

With ``--trace 0`` it reports the cell's end-to-end metrics, measured with
the program's tracing off; with ``--trace 1`` the per-layer metrics, from
the program's spans and the chip's profiler trace (the window is then at
most TRACE_SECONDS).  After the window it compares a sample of the served
requests, drawn from the seed, with the plain float32 reference in
``bench/reference/`` and sets ``correct``.  The last stdout line is the
result object; the numbers compared, each beside its limit, are the last
stderr lines.

``--calibrate <seeds>``, never used by a benchmark run, defines the limits:
it serves each seed's weights and traffic in one pair of processes and
judges the program's and the int8 control's readings by the same checks.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "src"))

#: JAX_PLATFORMS as the run was started (this process then pins itself to
#: the CPU; the destination gets the original)
PLATFORMS = os.environ.get("JAX_PLATFORMS")
TRACE_SECONDS = 10.0
SAMPLE_TOKENS = 256     # served tokens the comparison covers, at least
SAMPLE_MAX = 40         # requests compared, at most
PROBES = 512            # vocabulary ids whose received logits each call keeps
DRAIN_S = 60.0          # how long past the window a request may take
TENANT = "t0"           # the session's tenant, under which the weights sit
#: comparison readings (server.py's per-request keys), compared where the
#: configuration file gives a limit
READINGS = {"logit_err": "err", "max_logit_gap": "gap"}


def log(msg: str) -> None:
    print(f"[run {time.monotonic() - T_START:8.3f}s] {msg}", file=sys.stderr,
          flush=True)


def percentile(values, q: float) -> float:
    import numpy as np
    return float(np.percentile(np.asarray(values, np.float64), q))


# ---------------------------------------------------------------------------
# the destination process
# ---------------------------------------------------------------------------

class ServerProcess:
    """The destination, started at once so that it brings up the chip while
    this process loads the client; ``wait_ready`` blocks until it serves."""

    def __init__(self, config: str, seed: int, chips: int, trace: bool,
                 cmd=None, allow_cpu: bool = False) -> None:
        from bench import harness
        env = dict(os.environ)
        env["JAX_COMPILATION_CACHE_DIR"] = harness.COMPILE_CACHE
        env["AVEC_TRACE_ENABLED"] = "1" if trace else "0"
        env.pop("JAX_PLATFORMS", None)
        if allow_cpu:
            env["JAX_PLATFORMS"] = "cpu"
        elif PLATFORMS is not None:
            env["JAX_PLATFORMS"] = PLATFORMS
        argv = list(cmd or [sys.executable, os.path.join(harness.BENCH,
                                                         "server.py")])
        argv += ["--config", config, "--seed", str(seed),
                 "--tenant", TENANT, "--chips", str(chips)]
        if allow_cpu:
            argv.append("--allow-cpu")
        self.proc = subprocess.Popen(argv, cwd=ROOT, env=env, text=True,
                                     stdin=subprocess.PIPE,
                                     stdout=subprocess.PIPE)
        self.ready: dict = {}

    def wait_ready(self) -> dict:
        self.ready = self._recv()
        if self.ready.get("event") != "ready":
            raise RuntimeError(f"destination did not start: {self.ready}")
        return self.ready

    def _recv(self) -> dict:
        from bench import harness
        try:
            return harness.recv(self.proc.stdout)
        except EOFError:
            self.proc.wait(timeout=60)
            raise RuntimeError(f"destination process exited with code "
                               f"{self.proc.returncode}") from None

    def ask(self, cmd: str, **kw) -> dict:
        from bench import harness
        harness.send(self.proc.stdin, {"cmd": cmd, **kw})
        return self._recv()

    def close(self, timeout: float = 120) -> int:
        try:
            if self.proc.poll() is None:
                from bench import harness
                harness.send(self.proc.stdin, {"cmd": "exit"})
        except (BrokenPipeError, OSError):
            pass
        try:
            return self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            return self.proc.wait()


# ---------------------------------------------------------------------------
# the edge client
# ---------------------------------------------------------------------------

class Client:
    """The edge client: its own ``avec.connect`` client and session."""

    def __init__(self, address: str, cfg, shapes, probes) -> None:
        from repro import avec
        self.probes = probes
        self.conn = avec.connect([address], shadow_every=0, prefer_shm=False)
        self.sess = self.conn.session(cfg, shapes, "lm", tenant=TENANT)
        if not self.sess.runtime.has_model(self.sess.fp):
            raise RuntimeError(f"weights not installed under {self.sess.fp}")
        self.sess.ensure_model()
        self.n_calls = 0
        self.calls: list[tuple] = []      # (call_id, fn, n) in the window
        self.record = False

    def call(self, fn: str, tokens, n: int):
        """One call: the greedy token, and the received logits at the probe
        ids and at that token (what the comparison reads afterwards)."""
        import numpy as np
        self.n_calls += 1
        out = self.sess.call(fn, {"tokens": tokens})
        if self.record:
            self.calls.append((f"{self.sess._call_ns}-{self.n_calls}", fn, n))
        logits = out["logits"][0, -1]
        tok = int(np.argmax(logits))
        return tok, np.append(logits[self.probes], logits[tok]).astype(
            np.float32)

    def serve(self, req) -> dict:
        """One request: prefill, then greedy decode to its output length."""
        import numpy as np
        rec = {"rid": req.rid, "start": time.monotonic(), "times": [],
               "served": [], "probes": [], "prompt": req.prompt,
               "failed": None}
        P = len(req.prompt)
        try:
            tok, pr = self.call("prefill", req.prompt[None, :], P)
            rec["times"].append(time.monotonic())
            rec["served"].append(tok)
            rec["probes"].append(pr)
            for j in range(1, req.out_len):
                tok, pr = self.call("decode", np.array([[tok]], np.int32),
                                    P + j - 1)
                rec["times"].append(time.monotonic())
                rec["served"].append(tok)
                rec["probes"].append(pr)
        except Exception as e:  # noqa: BLE001 — a failed request is counted
            rec["failed"] = f"{type(e).__name__}: {e}"
        return rec

    def warm(self, lengths, vocab: int, rng) -> None:
        import numpy as np
        for P in lengths:
            toks = rng.integers(0, vocab, size=(1, P), dtype="int32")
            tok, _ = self.call("prefill", toks, P)
            self.call("decode", np.array([[tok]], np.int32), P)

    def close(self) -> None:
        self.conn.close()


def run_window(client, traffic, seconds: float) -> tuple:
    """Closed loop for ``seconds``: the next request goes out when the last
    one has finished; the one under way at the close runs to its end, and
    its tokens past the close are not counted.  Returns (records, t0)."""
    t0 = time.monotonic()
    t_end = t0 + seconds
    records: list[dict] = []
    stream = traffic.requests()
    while time.monotonic() < t_end:
        records.append(client.serve(next(stream)))
        if time.monotonic() > t_end + DRAIN_S:
            raise RuntimeError("a request ran a minute past the window")
    return records, t0


def sample(records, seed: int) -> list[dict]:
    """Completed requests to compare, drawn from the seed, with the longest
    among them: at least SAMPLE_TOKENS served tokens, at most SAMPLE_MAX."""
    from bench.traffic import _rng
    done = [r for r in records if r["failed"] is None and r["served"]]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r["prompt"]) + len(r["served"]))
    rest = [r for r in done if r is not longest]
    order = _rng(seed, 5).permutation(len(rest))
    pick, n = [longest], len(longest["served"])
    for i in order:
        if n >= SAMPLE_TOKENS or len(pick) >= SAMPLE_MAX:
            break
        pick.append(rest[i])
        n += len(rest[i]["served"])
    return pick


def items_of(picked) -> list[dict]:
    import numpy as np
    out = []
    for r in picked:
        P, served = len(r["prompt"]), r["served"]
        toks = np.concatenate([r["prompt"], np.asarray(served[:-1], np.int32)])
        out.append({"tokens": toks.tolist(),
                    "rows": list(range(P - 1, P - 1 + len(served))),
                    "served": [int(t) for t in served],
                    "probes": np.stack(r["probes"]).tolist()})
    return out


def end_to_end(records, t0: float, seconds: float) -> dict:
    ttft, tbt, tokens = [], [], 0
    for r in records:
        if r["times"]:
            ttft.append(r["times"][0] - r["start"])
            tbt.extend(b - a for a, b in zip(r["times"], r["times"][1:]))
            tokens += sum(1 for t in r["times"] if t0 <= t < t0 + seconds)
    out = {"tokens_per_s": tokens / seconds}
    if ttft:
        out["ttft_p50_ms"] = percentile(ttft, 50) * 1e3
        out["ttft_p95_ms"] = percentile(ttft, 95) * 1e3
    if tbt:
        out["tbt_p95_ms"] = percentile(tbt, 95) * 1e3
    return out, len(ttft), len(tbt)


def call_spans(client) -> list[dict]:
    """The program's spans of every call made in the window."""
    from repro.obs import trace as tr
    by_id = {t.call_id: t for t in tr.get_sink().recent(10 ** 9)}
    out = []
    for cid, fn, n in client.calls:
        t = by_id.get(cid)
        if t is None:
            continue
        if t.fn != fn:
            # the call ids mirror the session's own counter; a call the
            # mirror missed would pair every later span with the wrong call
            raise RuntimeError(f"trace {cid} is of {t.fn!r}, not {fn!r}")
        spans: dict[str, float] = {}
        for s in t.spans:
            spans[s["name"]] = spans.get(s["name"], 0.0) + s["dur_s"]
        out.append({"fn": fn, "n": n, "wall_s": t.wall_s, "spans": spans})
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def load_cell(workload: str, bench=None) -> tuple:
    """(benchmark, cell, configuration file path, traffic parameters)."""
    from bench import harness
    bench = bench or harness.load_benchmark()
    cell = harness.find(bench["workloads"], workload, "workload")
    return (bench, cell, harness.config_path(cell["config"]),
            harness.load_traffic(cell["traffic"]))


def setup(args, allow_cpu=False, server_cmd=None, spec=None):
    """Load the cell, start the destination, connect the client and warm
    up every prompt length the traffic uses.  ``spec`` replaces
    ``load_cell(args.workload)`` (tests of the harness)."""
    from bench import harness
    bench, cell, c_path, traffic_params = spec or load_cell(args.workload)
    server = ServerProcess(c_path, args.seed, int(cell["chips"]),
                           bool(args.trace), cmd=server_cmd,
                           allow_cpu=allow_cpu)
    client = None
    try:
        # the client side loads while the destination brings up the chip
        import numpy as np
        from bench import weights
        from bench.traffic import Traffic, _rng
        from repro.obs import trace as tr
        from repro.obs.config import global_config
        c = harness.load_config(c_path)
        traffic = Traffic(traffic_params, args.seed, c["vocab_size"])
        global_config().set("trace_enabled", bool(args.trace))
        tr._SINK = tr.TraceSink(capacity=10 ** 7)
        cfg = harness.program_config(c)
        shapes = weights.shapes(harness.reference_module(c["reference"])
                                .layout(c))
        probes = np.sort(_rng(args.seed, 6).choice(
            c["vocab_size"], min(PROBES, c["vocab_size"]), replace=False))
        ready = server.wait_ready()
        log(f"destination ready on {ready['device']} after "
            f"{ready['setup_s']:.3f}s ({ready['compiles']} compiles, "
            f"{ready['cache_hits']} cache hits; {ready['phases']})")
        client = Client(f"tcp://127.0.0.1:{ready['port']}", cfg, shapes,
                        probes)
        client.warm(traffic.shapes(), c["vocab_size"],
                    np.random.default_rng(0))
    except BaseException:
        if client is not None:
            client.close()
        server.close()
        raise
    return bench, cell, c, traffic_params, traffic, server, client


def measure(args, traffic, server, client):
    """One window; returns what the result line needs."""
    seconds = float(args.seconds)
    if args.trace:
        seconds = min(seconds, TRACE_SECONDS)
    client.calls, client.record = [], True
    server.ask("window_start", trace=bool(args.trace))
    records, t0 = run_window(client, traffic, seconds)
    client.record = False
    end = server.ask("window_end")
    window_s = end.get("window_s", seconds)
    if end.get("trace"):
        tr = end["trace"]
        log(f"trace: busy {tr['busy_s']:.6f}s of {window_s:.6f}s; module "
            f"seconds {tr['module_s']}, executions {tr['module_count']}; "
            f"scope seconds {tr['scope_s']}")
    log(f"counters in the window: "
        f"{ {k: v for k, v in end['counters'].items() if v} }")
    return {"records": records, "t0": t0, "seconds": seconds, "end": end,
            "window_s": window_s}


def compare(server, records, seed: int, probes, *, free: bool,
            control: bool):
    picked = sample(records, seed)
    if not picked:
        return None, 0, 0
    res = server.ask("reference", items=items_of(picked), seed=seed,
                     probe_ids=[int(i) for i in probes], free=free,
                     control=control)
    return res, len(picked), sum(len(r["served"]) for r in picked)


def judge(c: dict, items, failed: int) -> tuple:
    """The checks of one run and whether it is correct.  ``items`` holds the
    per-request readings (``None`` where nothing could be compared); the
    configuration file's ``limits`` name the readings compared, and one
    without a limit there is logged only."""
    checks = {}
    for name, key in READINGS.items():
        value = max(it[key] for it in items) if items else float("inf")
        if name in c["limits"]:
            checks[name] = {"value": value, "limit": c["limits"][name]}
        else:
            log(f"reading {name}: {value!r} (not compared)")
    checks["failed_requests"] = {"value": failed, "limit": 0}
    correct = bool(items) and all(ck["value"] <= ck["limit"]
                                  for ck in checks.values())
    return checks, correct


def result_line(args, bench, cell, c, server, client, m, ref) -> dict:
    from bench import flops, harness
    records = m["records"]
    attempted = len(records)
    failed = sum(1 for r in records if r["failed"] is not None)
    for r in records:
        if r["failed"]:
            log(f"request {r['rid']} failed: {r['failed']}")
    e2e, n_ttft, n_tbt = end_to_end(records, m["t0"], m["seconds"])
    dev = dict(server.ready["device"])
    dev["memory_peak_bytes"] = m["end"]["peak_bytes"]
    metrics = {}
    if args.trace:
        tr = m["end"]["trace"]
        dev["busy_s"] = tr["busy_s"]
        dev["window_s"] = m["window_s"]
        ctx = {"c": c, "calls": call_spans(client), "trace": tr,
               "counters": m["end"]["counters"], "window_s": m["window_s"],
               "peak": flops.peaks(dev["kind"])}
        for spec in harness.per_layer_for(bench, cell["name"]):
            v = harness.metric_reader(spec["name"]).read(ctx)
            if v is not None:
                metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
    else:
        e2e["setup_s"] = m["setup_s"]
        for spec in harness.end_to_end_for(bench, cell["name"]):
            if spec["name"] in e2e:
                metrics[spec["name"]] = {"value": e2e[spec["name"]],
                                         "unit": spec["unit"]}
    log(f"window {m['seconds']:.3f}s: {attempted} requests, {failed} failed, "
        f"{n_ttft} first tokens, {n_tbt} token gaps; compiles in window "
        f"{m['end']['compiles']}")
    checks, correct = judge(c, ref["items"] if ref else [], failed)
    log(f"compared {m['sample_requests']} requests, {m['sample_tokens']} "
        f"served tokens, with the float32 reference in "
        f"{ref['ref_s'] if ref else 0.0:.3f}s")
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": dev}
    if args.trace:
        tr = m["end"]["trace"]
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
    out["checks"] = checks
    for name, ck in checks.items():
        print(f"check {name}: {ck['value']!r} (limit {ck['limit']!r})",
              file=sys.stderr, flush=True)
    return out


def run(args, allow_cpu: bool = False, server_cmd=None, spec=None) -> dict:
    bench, cell, c, _, traffic, server, client = setup(
        args, allow_cpu, server_cmd, spec)
    try:
        m = measure(args, traffic, server, client)
        m["setup_s"] = m["t0"] - T_START
        client.close()
        ref, m["sample_requests"], m["sample_tokens"] = compare(
            server, m["records"], args.seed, client.probes, free=True,
            control=False)
        return result_line(args, bench, cell, c, server, client, m, ref)
    finally:
        client.close()
        rc = server.close()
        if rc != 0:
            log(f"destination process exited with code {rc}")


def calibrate(args, seeds) -> None:
    """The program's and the control's comparison numbers on many seeds,
    in one pair of processes: per seed, fresh weights and traffic, one
    window at the cell's load, the reference and the int8 control, each
    judged by ``judge`` as a run is."""
    from bench.traffic import Traffic
    args.seed = seeds[0]
    bench, cell, c, params, traffic, server, client = setup(args)
    try:
        for seed in seeds:
            if seed != args.seed:
                server.ask("reseed", seed=seed)
            tr = Traffic(params, seed, c["vocab_size"])
            m = measure(args, tr, server, client)
            ref, n_req, n_tok = compare(server, m["records"], seed,
                                        client.probes, free=False,
                                        control=True)
            items = ref["items"] if ref else []
            failed = sum(1 for r in m["records"] if r["failed"])
            _, ok = judge(c, items, failed)
            _, ctrl_ok = judge(c, [{"err": i["ctrl_err"], "gap": i["ctrl_gap"]}
                                   for i in items], 0)
            nums = {k: max((i[k] for i in items), default=None)
                    for k in ("gap", "ctrl_gap", "err", "ctrl_err")}
            print(json.dumps({
                "seed": seed, "requests": len(m["records"]),
                "compared": n_req, "tokens": n_tok, **nums,
                "correct": ok, "control_correct": ctrl_ok,
                "ref_s": ref["ref_s"] if ref else None,
                "compiles_in_window": m["end"]["compiles"]}), flush=True)
    finally:
        client.close()
        server.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--calibrate", default="",
                    help="comma-separated seeds: read the comparison and "
                         "its int8 control on each (defines the limits)")
    args = ap.parse_args(argv)
    # the edge side never takes the chip: pin this process to the CPU
    # before JAX starts; both processes keep their compile cache in the
    # checkout, without eviction (see server.py)
    os.environ["JAX_PLATFORMS"] = "cpu"
    from bench import harness
    os.environ["JAX_COMPILATION_CACHE_DIR"] = harness.COMPILE_CACHE
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ["AVEC_TRACE_ENABLED"] = "1" if args.trace else "0"
    if args.calibrate:
        calibrate(args, [int(s) for s in args.calibrate.split(",")])
        return 0
    out = run(args)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
