"""What the `/generate` traffic kinds share: the served model behind the
program's gateway, requests drawn from the seed, the HTTP client, the
window's arithmetic, and the comparison of served tokens with the plain
reference. `generate_closed` and `generate_open` differ only in who
decides when the next request is sent."""
from __future__ import annotations

import gc
import http.client
import json
import math
import threading
import time
import urllib.parse
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np

from benchmark import manifest, stats

HOST_SPANS = ("serve/generate", "engine/prefill_group", "engine/step",
              "kv/batch_view", "model/step", "model/prefill")
MODEL_NAME = "lm"


def draw_requests(seed: int, traffic: dict, vocab: int) -> List[dict]:
    """`pool` requests. The prompt lengths are the quantile grid of a
    log-normal with the given median and sigma, clipped: `grid` of them
    (default: `pool`), and the pool is made of blocks that are each the
    whole grid in a shuffled order, so any stretch of traffic holds the
    same sizes. The seed draws the token ids. It also draws the order,
    unless the mix fixes one with `order_seed`: the engine's step costs by
    the longest row of a batch, so another order is other work (tokens/s
    spread by 6.1% over six seeds and under 1% between two runs of one
    seed; my chip runs, PR 24), and a mix whose cell is held to a bound
    gives every seed the same sizes in the same order."""
    n = int(traffic["pool"])
    grid = int(traffic.get("grid", n))
    p = traffic["prompt_len"]
    nd = NormalDist(math.log(p["median"]), p["sigma"])
    sizes = [int(min(p["max"], max(p["min"], round(math.exp(
        nd.inv_cdf((i + 0.5) / grid)))))) for i in range(grid)]
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(traffic["order_seed"]) \
        if "order_seed" in traffic else rng
    lens: List[int] = []
    while len(lens) < n:
        block = list(sizes)
        order.shuffle(block)
        lens += block
    return [dict(idx=i, prompt=rng.integers(0, vocab, ln).tolist(),
                 max_new_tokens=int(traffic["max_new_tokens"]))
            for i, ln in enumerate(lens[:n])]


class Client:
    """One keep-alive HTTP connection; `send` posts one request and waits
    for its whole reply."""

    def __init__(self, url: str, timeout: float = 600.0):
        u = urllib.parse.urlparse(url)
        self._host, self._port, self._timeout = u.hostname, u.port, timeout
        self._conn: Optional[http.client.HTTPConnection] = None

    def send(self, path: str, payload: dict):
        body = json.dumps(payload).encode()
        try:
            return self._once(path, body)
        except (http.client.HTTPException, ConnectionError, OSError):
            self.close()            # the server closed an idle connection
            return self._once(path, body)

    def _once(self, path: str, body: bytes):
        if self._conn is None:
            self._conn = http.client.HTTPConnection(
                self._host, self._port, timeout=self._timeout)
        self._conn.request("POST", path, body,
                           {"Content-Type": "application/json"})
        r = self._conn.getresponse()
        return r.status, json.loads(r.read())

    def close(self):
        if self._conn is not None:
            self._conn.close()
            self._conn = None


def tokens_made() -> float:
    """The program's own count of the tokens its decode engine has made."""
    from benchmark.probes import counter_totals
    return counter_totals().get("serving_decode_tokens_total", 0.0)


def _spanned(name: str, fn):
    """The benchmark's own span around a call into a layer, recorded in
    the program's span ring (perf_counter clock) beside its own spans."""
    from deeplearning4j_tpu.optimize import tracing

    def wrapped(*a, **kw):
        t0 = time.perf_counter()
        try:
            return fn(*a, **kw)
        finally:
            tracing.add_span(name, t0, time.perf_counter() - t0, cat="bench")
    return wrapped


class GenerateKind:
    host_spans = HOST_SPANS

    def __init__(self, ctx):
        self.ctx = ctx
        self.records: List[dict] = []
        self._lock = threading.Lock()
        self.gw = self.model = self._weights = None

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        from deeplearning4j_tpu.serving import ServingGateway
        cfg, t = self.ctx.cfg, self.ctx.traffic
        self.model = manifest.resolve(cfg["builder"])(cfg, self.ctx.seed,
                                                      self.ctx.chips)
        eng = cfg["engine"]
        self.gw = ServingGateway(pool_size=int(t.get("http_pool", 16)))
        self.entry = self.gw.add_decode_model(
            MODEL_NAME, self.model,
            max_decode_batch=eng["max_decode_batch"],
            queue_limit=eng.get("queue_limit", 64),
            pack_bucket=eng["pack_bucket"],
            kv_block_tokens=eng["kv_block_tokens"],
            kv_max_blocks=eng["kv_max_blocks"])
        self.gw.warmup()
        self.gw.start()
        self.requests = draw_requests(self.ctx.seed, t, cfg["vocab_size"])

    def instrument(self) -> None:
        """Traced runs only: the benchmark's own spans around the calls
        into each layer, and the program's flight recorder."""
        from deeplearning4j_tpu.serving import flight_recorder
        flight_recorder.enable()
        ad = self.entry.engine.adapter
        ad.step = _spanned("engine/step", ad.step)
        ad.prefill_group = _spanned("engine/prefill_group",
                                      ad.prefill_group)
        ad.cache.batch_view = _spanned("kv/batch_view",
                                         ad.cache.batch_view)
        self.model.step = _spanned("model/step", self.model.step)
        self.model.prefill = _spanned("model/prefill", self.model.prefill)

    # ----------------------------------------------------------- one request
    def post(self, client: Client, req: dict, due: Optional[float] = None):
        """Send one request and wait for its whole reply. Latency runs
        from `due` (open loop) or from the send."""
        t_send = time.perf_counter()
        rec = dict(idx=req["idx"], t_due=t_send if due is None else due,
                   t_send=t_send, ok=False, tokens=[], trace=None)
        try:
            code, body = client.send("/generate", {
                "model": MODEL_NAME, "prompt": req["prompt"],
                "max_new_tokens": req["max_new_tokens"]})
            rec["ok"] = code == 200 and body.get("status") == "ok" \
                and len(body.get("tokens", [])) == req["max_new_tokens"]
            rec["tokens"] = body.get("tokens", [])
            rec["trace"] = body.get("trace")
            if not rec["ok"]:
                rec["error"] = f"{code} {str(body)[:200]}"
        except Exception as e:  # a failed request; counted, never hidden
            rec["error"] = f"{type(e).__name__}: {e}"
        rec["t_done"] = time.perf_counter()
        with self._lock:
            self.records.append(rec)
        return rec

    # ------------------------------------------------------------ window
    def drive(self, t0: float, t1: float) -> dict:
        """Send the traffic; return once every request sent before `t1`
        has its reply. Subclasses implement it."""
        raise NotImplementedError

    def run(self, seconds: float, probe) -> dict:
        ramp = float(self.ctx.traffic.get("ramp_seconds", 0.0))
        if probe.trace:
            self.instrument()
        start = time.perf_counter()
        t0, t1 = start + ramp, start + ramp + seconds
        made = {}       # the program's own count of tokens, at t0 and t1

        def opened():
            made["t0"] = tokens_made()
            probe.open()

        def closed():
            made["t1"] = tokens_made()

        timers = [threading.Timer(ramp, opened),
                  threading.Timer(ramp + seconds, closed)]
        for timer in timers:
            timer.start()
        extra = self.drive(start, t1)
        for timer in timers:
            timer.join()
        probe.close()
        extra["counter_tokens"] = made["t1"] - made["t0"]
        return self.reduce(t0, t1, extra)

    def reduce(self, t0: float, t1: float, extra: dict) -> dict:
        """All requests due in the window count for the tail. For the rate,
        each request's tokens are spread evenly over the time it was in
        flight and count as far as that time lies in the window: all the
        work of the window over all its time. (Counting whole replies as
        they complete gives the same over a long window, but eight
        lock-stepped rows complete in waves of 256 tokens, and 51 s hold
        four or five of them: 20.1 or 25.1 tokens/s by the seed's phase;
        my chip runs, PR 24.)"""
        recs = self.records
        sent = [r for r in recs if t0 <= r["t_due"] < t1]
        failed = [r for r in sent if not r["ok"]]
        lat = [(r["t_done"] - r["t_due"]) * 1e3 for r in sent if r["ok"]]
        worst = max(lat + [(t1 - t0) * 1e3])
        lat += [worst] * len(failed)
        tokens = prompt_tokens = 0.0
        for r in recs:
            inside = min(r["t_done"], t1) - max(r["t_due"], t0)
            if r["ok"] and inside > 0:
                share = inside / (r["t_done"] - r["t_due"])
                tokens += share * len(r["tokens"])
                prompt_tokens += share * len(
                    self.requests[r["idx"]]["prompt"])
        done = [r for r in recs if r["ok"] and t0 <= r["t_done"] < t1]
        out = dict(t0=t0, t1=t1, attempted=len(sent), failed=len(failed),
                   completed=len(done), tokens=tokens,
                   prompt_tokens=prompt_tokens, latencies_ms=lat,
                   errors=[r.get("error") for r in failed][:3],
                   metrics={
                       "generate_tokens_per_s": stats.rate(tokens, t0, t1),
                       "request_p95_ms": stats.percentile(lat, 95.0)})
        out.update(extra)
        print(f"info request_p95_ms over {len(lat)} requests", flush=True)
        # the rate's cross-checks: whole replies as they completed, and
        # the program's own counter of tokens made between t0 and t1
        whole = sum(len(r["tokens"]) for r in done)
        counted = out.get("counter_tokens", float("nan"))
        print(f"info generate_tokens_per_s: {tokens:.1f} tokens lie in the "
              f"window; {len(done)} replies completed in it ({whole} "
              f"tokens, {stats.rate(whole, t0, t1):.3f}/s); "
              f"serving_decode_tokens_total rose by {counted:.0f} "
              f"({stats.rate(counted, t0, t1):.3f}/s)", flush=True)
        self.window = out
        return out

    # ------------------------------------------------ after the window
    def temporaries_bytes(self) -> int:
        return 0

    def release(self) -> None:
        from deeplearning4j_tpu.serving import flight_recorder
        self.kv_left = self.entry.engine.adapter.cache.blocks_in_use()
        self.gw.stop()
        flight_recorder.disable()
        self.gw = self.model = self.entry = None
        gc.collect()

    def sample(self) -> List[dict]:
        """Requests the window finished, drawn from the seed, the longest
        among them: some hundreds of served tokens. Every request is
        greedy (`/generate` takes no sampling parameters)."""
        w = self.window
        pool = sorted((r for r in self.records if r["ok"]
                       and w["t0"] <= r["t_done"] < w["t1"]),
                      key=lambda r: r["idx"])
        if not pool:
            return []
        k = min(int(self.ctx.traffic.get("check_requests", 12)), len(pool))
        longest = max(pool, key=lambda r: (
            len(self.requests[r["idx"]]["prompt"]) + len(r["tokens"]),
            -r["idx"]))
        rng = np.random.default_rng(self.ctx.seed + 1)
        picks = {longest["idx"]: longest}
        for i in rng.permutation(len(pool)):
            if len(picks) >= k:
                break
            picks.setdefault(pool[i]["idx"], pool[i])
        return list(picks.values())

    def check(self, control=None) -> Dict[str, float]:
        """The widest gap by which a served token's logit lies below the
        reference's best. With `control` (the builder's tool and the
        tests, never the benchmark's own command) the token that the
        reference in that lower precision puts first, at each position of
        the same prompts and served tokens, stands in the served token's
        place."""
        cfg = self.ctx.cfg
        picks = self.sample()
        if not picks:
            return {"served_gap": float("nan"), "kv_blocks_left": self.kv_left}
        if self._weights is None:
            self._weights = manifest.resolve(cfg["weights"])(self.ctx.seed,
                                                             cfg)
        seqs = [(self.requests[r["idx"]]["prompt"], r["tokens"])
                for r in picks]
        gaps = manifest.resolve(cfg["reference"])(
            self._weights, cfg["num_attention_heads"], seqs,
            cfg["max_context"] + 1, lowp=control)
        which = 0 if control is None else 1
        n_tok = sum(len(g[0]) for g in gaps)
        print(f"info check: {len(picks)} requests, {n_tok} served tokens "
              f"compared", flush=True)
        return {"served_gap": max(float(g[which].max()) for g in gaps),
                "kv_blocks_left": float(self.kv_left)}
