"""Traffic kind `generate_closed`: `clients` threads in a closed loop,
each a real HTTP `POST /generate` that waits for its whole reply before it
sends the next. Parameters (traffic file): `clients`, `pool`,
`prompt_len` {median, sigma, min, max}, `max_new_tokens`, `ramp_seconds`,
`http_pool`, `check_requests`."""
from __future__ import annotations

import threading
import time

from benchmark.kinds._generate import Client, GenerateKind


class Kind(GenerateKind):
    def drive(self, start: float, t1: float) -> dict:
        n = int(self.ctx.traffic["clients"])

        def client(c: int):
            conn = Client(self.gw.url)
            try:
                for req in self.requests[c::n]:
                    if time.perf_counter() >= t1:
                        return
                    self.post(conn, req)
            finally:
                conn.close()

        threads = [threading.Thread(target=client, args=(c,),
                                    name=f"bench-client-{c}")
                   for c in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return {"clients": n}
