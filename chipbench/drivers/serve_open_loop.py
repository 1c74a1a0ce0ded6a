"""Open loop against one in-process serving front: Poisson arrivals at
the traffic file's fixed rate, ``InferenceClient``s on the copied
``open_loop`` generator, ego-subgraphs with features and labels in the
reply.  The rate is data: it is found once by a sweep, never here."""
from __future__ import annotations

import numpy as np

from chipbench import checks
from chipbench import data
from chipbench import openloop
from chipbench.common import Window, span, timed


def draw_requests(traffic: dict, num_nodes: int, count: int, rng):
    """``count`` seed sets: sizes k in [min, max] with P(k) ~ k^power,
    ids Zipf(``id_zipf``) over a seeded permutation of the nodes, so the
    hot ids are the same few all through the run."""
    spr = traffic["seeds_per_request"]
    ks = np.arange(int(spr["min"]), int(spr["max"]) + 1)
    p = ks.astype(np.float64) ** float(spr["power"])
    sizes = rng.choice(ks, size=count, p=p / p.sum())
    a = float(traffic["id_zipf"])
    perm = rng.permutation(num_nodes)
    u = rng.random(int(sizes.sum()))
    # Inverse CDF of the continuous law with density ~ r^-a on [1, N + 1).
    rank = ((u * ((num_nodes + 1.0) ** (1.0 - a) - 1.0) + 1.0)
            ** (1.0 / (1.0 - a))).astype(np.int64) - 1
    ids = perm[np.clip(rank, 0, num_nodes - 1)]
    out, off = [], 0
    for k in sizes.tolist():
        # The server answers for distinct seeds: a set is what is asked.
        out.append(np.asarray(list(dict.fromkeys(ids[off: off + k]
                                                 .tolist())), np.int64))
        off += k
    return out


class Driver:
    def __init__(self, env):
        from glt_tpu.distributed import init_server
        from glt_tpu.serving import InferenceClient, ServingOptions

        self.env = env
        cfg, sam, tr = env.config, env.config["sampling"], env.traffic
        self.fanout = list(sam["fanout"])
        with timed(env.log, "generate + place"):
            self.d = data.build_one_chip(cfg, env.seed, env.devices[0],
                                         env.log)
        self.srv = init_server(
            self.d.dataset, enable_metrics=env.trace,
            serving=ServingOptions(
                num_neighbors=self.fanout,
                seed_buckets=tuple(tr["seed_buckets"]),
                max_seeds_per_request=int(tr["seeds_per_request"]["max"]),
                frontier_cap=sam["frontier_cap"], seed=env.seed))
        with timed(env.log, "engine.warmup (compile or cache)"):
            self.srv.serving.engine.warmup()
        self.threads = int(tr["client_threads"])
        self.timeout = float(tr["client_timeout_s"])
        self.clients = [InferenceClient(self.srv.addr, timeout=self.timeout)
                        for _ in range(self.threads)]
        self.rng = np.random.default_rng([env.seed, 17])
        # One reply per bucket through the whole client path, so the
        # window opens with every connection and code path warm.
        most = int(tr["seeds_per_request"]["max"])
        for k in tr["seed_buckets"]:
            self.clients[0].subgraph(
                self.rng.choice(self.d.shapes.num_nodes,
                                size=min(int(k), most), replace=False),
                timeout=120.0)

    def _send(self, worker: int, seeds) -> None:
        with span("request"):
            reply = self.clients[worker].subgraph(seeds)
        if np.asarray(reply.batch).tolist() != seeds.tolist():
            raise checks.CheckFailure("reply echoes other seeds")

    def window(self, seconds: float) -> Window:
        rate = float(self.env.traffic["rate_rps"])
        arrivals = openloop.poisson_arrivals(rate, seconds, self.rng)
        requests = draw_requests(self.env.traffic,
                                 self.d.shapes.num_nodes, len(arrivals),
                                 self.rng)
        outs = openloop.run(self._send, requests, arrivals, self.threads,
                            join_s=self.timeout + 60.0)
        ok = np.asarray([o.latency_s for o in outs if o.kind == "ok"])
        late = np.asarray([o.late_s for o in outs])
        kinds = {}
        for o in outs:
            kinds[o.kind] = kinds.get(o.kind, 0) + 1
        self.env.log(f"outcomes {kinds}")
        # A failed request counts as missing its limit: it enters the
        # percentiles at the client's time limit, which it overran.
        lat = np.concatenate([ok, np.full(len(outs) - ok.size,
                                          self.timeout)])
        metrics = {f"latency_p{q}_ms": float(np.percentile(lat, q)) * 1e3
                   for q in (50, 90, 95, 99)}
        metrics["latency_mean_ms"] = float(lat.mean()) * 1e3
        done_s = max((o.due_s + o.latency_s for o in outs
                      if o.kind == "ok"), default=0.0)
        return Window(
            attempted=len(outs), failed=len(outs) - int(ok.size),
            metrics=metrics, steps=int(ok.size),
            counters={**metrics, "gen_late_ms_p99":
                      float(np.percentile(late, 99)) * 1e3,
                      "offered_rps": rate,
                      "completed_rps": ok.size / max(done_s, 1e-9),
                      "last_done_s": done_s,
                      "seeds_mean": float(np.mean(
                          [r.size for r in requests])),
                      "outcomes": kinds, "window_s": seconds})

    def check(self) -> dict:
        rng = np.random.default_rng([self.env.seed, 13])
        n = self.d.shapes.num_nodes
        for k in self.env.traffic["check_requests"]:
            seeds = rng.choice(n, size=int(k), replace=False)
            reply = self.clients[0].subgraph(seeds, timeout=120.0)
            checks.check_reply(self.d.ref, reply, seeds.tolist(),
                               self.fanout, rng)
        return {}

    def close(self) -> None:
        for c in self.clients:
            c.close()
        self.srv.shutdown()
