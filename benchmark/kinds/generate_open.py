"""Traffic kind `generate_open`: requests are due on a schedule drawn from
the seed whether or not earlier ones have finished. Each is timed from
when it was due, and the generator's own lateness is reported. Parameters
(traffic file) as `generate_closed`, without `clients`, plus `rate`
(requests/s), `burst` {every_s, size} (optional: `size` requests arrive
together every `every_s` seconds on top of the steady Poisson stream) and
`senders` (threads that carry the requests)."""
from __future__ import annotations

import queue
import threading
import time

import numpy as np

from benchmark.kinds._generate import Client, GenerateKind


def schedule(seed: int, traffic: dict, horizon_s: float) -> list:
    """Due times (seconds from the start) up to `horizon_s`: a Poisson
    stream at `rate`, plus the bursts."""
    rng = np.random.default_rng(seed + 2)
    due, t = [], 0.0
    while True:
        t += rng.exponential(1.0 / float(traffic["rate"]))
        if t >= horizon_s:
            break
        due.append(t)
    burst = traffic.get("burst")
    if burst:
        k = 1
        while k * burst["every_s"] < horizon_s:
            due += [k * burst["every_s"]] * int(burst["size"])
            k += 1
    return sorted(due)


class Kind(GenerateKind):
    def drive(self, start: float, t1: float) -> dict:
        due = schedule(self.ctx.seed, self.ctx.traffic, t1 - start)
        if len(due) > len(self.requests):
            raise ValueError(f"schedule wants {len(due)} requests, the pool "
                             f"holds {len(self.requests)}")
        q: "queue.Queue" = queue.Queue()
        late = []

        def sender():
            conn = Client(self.gw.url)
            try:
                while True:
                    item = q.get()
                    if item is None:
                        return
                    req, t_due = item
                    late.append(time.perf_counter() - t_due)
                    self.post(conn, req, due=t_due)
            finally:
                conn.close()

        threads = [threading.Thread(target=sender, name=f"bench-sender-{i}")
                   for i in range(int(self.ctx.traffic.get("senders", 64)))]
        for t in threads:
            t.start()
        for req, d in zip(self.requests, due):
            wait = start + d - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            q.put((req, start + d))
        for _ in threads:
            q.put(None)
        for t in threads:
            t.join()
        return {"generator_late_ms_max": max(late, default=0.0) * 1e3,
                "offered": len(due)}
