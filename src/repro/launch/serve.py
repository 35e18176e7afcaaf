"""Serving launcher CLI — Metronome retrieval in front of the
continuous-batching engine.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --smoke \
      --requests 20 --rate 40

Drives a Poisson request load and reports the paper's metrics (host CPU
fraction, TTFT, retrieval latency) for Metronome vs the busy-poll
baseline.
"""

from __future__ import annotations

import argparse
import functools
import time
from dataclasses import dataclass

import jax
import numpy as np

from repro.configs import get_config, list_configs
from repro.configs.base import ModelConfig
from repro.core import MetronomeConfig
from repro.models import Model
from repro.runtime import BusyPollPolicy, FixedPeriodPolicy, MetronomePolicy
from repro.runtime.stats import RunStats
from repro.serving import EngineConfig, InferenceEngine, Request, Server


@dataclass
class Served:
    """What one launcher run served: the model it built, the requests
    with their generated tokens, and the server's stats."""

    cfg: ModelConfig
    model: Model
    params: dict
    engine_cfg: EngineConfig
    mode: str
    policy: object
    requests: list[Request]
    stats: RunStats
    completed: int
    ok: bool


def _wait_all(reqs: list[Request], server: Server, timeout_s: float) -> bool:
    """Wait for every request; give up early once a poller has died."""
    deadline = time.monotonic() + timeout_s
    for r in reqs:
        while not r.wait(0.05):
            if server.error is not None or time.monotonic() > deadline:
                return False
    return True


def serve(argv=None) -> Served:
    """Build the model from ``PRNGKey(0)``, serve a Poisson request load
    behind the chosen retrieval policy, and return what was served.
    Raises the first poller exception, if a poller died."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True, choices=list_configs())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=20)
    ap.add_argument("--rate", type=float, default=40.0)
    ap.add_argument("--max-new", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--pollers", type=int, default=3)
    ap.add_argument("--v-target-us", type=float, default=3_000.0)
    ap.add_argument("--policy", default="metronome",
                    choices=("metronome", "busy-poll", "fixed-period"),
                    help="retrieval policy (repro.runtime)")
    ap.add_argument("--busy-poll", action="store_true",
                    help="deprecated alias for --policy busy-poll")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    model = Model(cfg)
    params = jax.jit(functools.partial(model.init, max_seq=args.max_len))(
        jax.random.PRNGKey(0))
    engine_cfg = EngineConfig(max_slots=args.slots, max_len=args.max_len,
                              prefill_buckets=(8, 16))
    engine = InferenceEngine(model, params, engine_cfg)
    warm = Request(prompt=[1, 2], max_new_tokens=2)
    engine.submit([warm])
    engine.pump()

    if args.busy_poll and args.policy != "metronome":
        ap.error("--busy-poll (deprecated) conflicts with an explicit "
                 "--policy; pass --policy busy-poll instead")
    mode = "busy-poll" if args.busy_poll else args.policy
    if mode == "busy-poll":
        policy = BusyPollPolicy()
    elif mode == "fixed-period":
        policy = FixedPeriodPolicy(args.v_target_us, threads=1)
    else:
        policy = MetronomePolicy(
            MetronomeConfig(m=args.pollers, v_target_us=args.v_target_us,
                            t_long_us=args.v_target_us * 20))
    server = Server(engine, policy)
    server.start()
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(args.requests):
        r = Request(prompt=[(i % (cfg.vocab_size - 3)) + 1, 2, 3],
                    max_new_tokens=args.max_new)
        server.submit(r)
        reqs.append(r)
        time.sleep(rng.exponential(1.0 / args.rate))
    ok = _wait_all(reqs, server, 60.0)
    stats = server.stop()
    completed = sum(len(r.tokens) == args.max_new for r in reqs)
    return Served(cfg=cfg, model=model, params=params, engine_cfg=engine_cfg,
                  mode=mode, policy=policy, requests=reqs, stats=stats,
                  completed=completed, ok=ok)


def main(argv=None) -> int:
    s = serve(argv)
    reqs = s.requests
    ttft = np.median([(r.first_token_ns - r.arrival_ns) / 1e6 for r in reqs])
    print(f"arch={s.cfg.name} mode={s.mode} "
          f"completed={s.completed}/{len(reqs)} "
          f"cpu={s.stats.cpu_fraction:.3f} ttft_ms={ttft:.2f}")
    if s.mode == "metronome":
        ctrl = s.policy.controller
        print(f"controller: rho={ctrl.rho:.3f} T_S={ctrl.t_short_us:.0f}us "
              f"cycles={ctrl.cycles}")
    return 0 if s.ok else 1


if __name__ == "__main__":
    from repro.launch import jax_cache

    jax_cache.enable()
    raise SystemExit(main())
