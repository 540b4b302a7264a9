"""Smoke run of the Mirage decision path on one TPU chip.

    python chip_smoke.py

One process, no arguments. It drives the library calls that
``repro.launch.provision`` makes, with the agent at its configured width
(configs/mirage_agent.py: 4 layers, d=256, 10 experts, bf16 compute) over the
registry's 144-snapshot history at 600 s intervals, weights from a seed:

1. synthesise the V100/heavy/single trace and build its env;
2. collect offline samples;
3. pretrain and train ``moe+dqn`` online (DQN updates on the chip);
4. one policy-gradient update at the same width;
5. evaluate the learned policy and ``reactive`` over a few lanes;
6. serve 8 tenants through ``ProvisionService`` with no fallback;
7. compare ``q_values`` on the TPU with the same program on the CPU.

It exits non-zero, printing no result line, unless JAX's first device is a
TPU. Times printed before the last line are smoke timings of one run, not
benchmark metrics. The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SCENARIO = "V100/heavy/single"
HISTORY, INTERVAL = 144, 600.0
SEED = 0
OFFLINE_EPISODES, OFFLINE_POINTS = 4, 4   # 16 samples: one pretrain batch
PRETRAIN_EPOCHS = 2
ONLINE_EPISODES = 32
EVAL_LANES = 4
TENANTS = 8
Q_TOL = 1e-2          # |q_tpu - q_cpu| <= Q_TOL * max(1, |q_cpu|): a few
                      # bf16 rounding units (2^-8) of the largest Q-value


def _say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _check(ok, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


@contextlib.contextmanager
def _phase(name: str, log: "CompileLog"):
    c0, t0 = log.total_s(), time.perf_counter()
    yield
    _say(f"{name}: {time.perf_counter() - t0:.1f}s wall "
         f"({log.total_s() - c0:.1f}s compiling)  [smoke timing]")


def smoke(log: "CompileLog") -> None:
    """Run the seven phases; raises RuntimeError on a failed check."""
    import jax
    import numpy as np
    from repro.core import (PGConfig, PGLearner, ReplayCheckpointCache,
                            build_policy, collect_offline_samples,
                            evaluate_batch, q_values)
    from repro.serve import ProvisionService, ServiceConfig
    from repro.sim import get_scenario, make_vector_env

    with _phase("1 trace + env", log):
        scenario = get_scenario(SCENARIO)
        trace = scenario.make_trace(months=1, seed=SEED)
        cfg = scenario.env_config(HISTORY, INTERVAL)
        cache = ReplayCheckpointCache(trace, cfg.n_nodes)
        env = scenario.make_env(seed=SEED, history=HISTORY,
                                interval=INTERVAL, cache=cache, trace=trace)
        _say(f"{SCENARIO}: {len(trace)} jobs, {cfg.n_nodes} nodes, "
             f"history {cfg.history} x {cfg.interval:.0f}s")

    with _phase("2 offline samples", log):
        samples = collect_offline_samples(env, n_episodes=OFFLINE_EPISODES,
                                          n_points=OFFLINE_POINTS, seed=SEED)
        _say(f"{len(samples)} offline samples")

    with _phase("3 pretrain + online DQN", log):
        policy = build_policy("moe+dqn", env, offline_samples=samples,
                              online_episodes=ONLINE_EPISODES,
                              pretrain_epochs=PRETRAIN_EPOCHS,
                              history=HISTORY, reduced=False, seed=SEED)
        learner = policy.learner
        fc = learner.fc
        n_params = sum(int(a.size) for a in jax.tree.leaves(learner.params))
        _say(f"moe+dqn: {fc.n_experts} experts x {fc.trunk.n_layers} "
             f"layers, d={fc.trunk.d_model}, {n_params:,} params; "
             f"{learner._steps} DQN updates on "
             f"{jax.tree.leaves(learner.params)[0].devices()}")
        _check(learner._steps > 0, "no DQN update ran")

    states = np.stack([s["matrix"] for s in samples]).astype(np.float32)
    with _phase("4 PG update", log):
        pg = PGLearner(fc, PGConfig(), seed=SEED, params=learner.params)
        actions = learner.act_batch(states, explore=False)
        loss = pg.train_on_episode(states, actions,
                                   float(np.mean([s["reward"]
                                                  for s in samples])))
        _say(f"1 PG update, loss {loss:.6f}")
        _check(np.isfinite(loss), f"PG loss {loss}")

    with _phase("5 evaluate", log):
        venv = make_vector_env(trace, cfg, EVAL_LANES, seed=SEED,
                               cache=cache)
        for pol in (policy, build_policy("reactive", env)):
            res = evaluate_batch(venv, pol, seed=SEED + 1)
            summary = res.summary()
            _say(f"{res.method}: {json.dumps(summary)}")
            _check(summary["n_episodes"] == EVAL_LANES, str(summary))

    with _phase("6 service", log):
        service = ProvisionService(trace, cfg, policy,
                                   svc=ServiceConfig(tenants=TENANTS,
                                                     links=1),
                                   seed=SEED, cache=cache)
        sres = service.run()
        _say(f"service ({TENANTS} tenants x 1 link, max_batch "
             f"{service.svc.max_batch}): {sres.reason}; "
             f"{sres.n_decisions} decisions in {sres.n_batches} batches, "
             f"{sres.n_degraded} degraded, {sres.breaker_trips} breaker "
             f"trips, fallbacks {[t.n_fallbacks for t in sres.tenants]}")
        if service.policy.last_error:
            _say(f"last fallback error: {service.policy.last_error}")
        _check(all(t.reason == "completed" for t in sres.tenants),
               "every tenant completes")
        _check(all(t.n_fallbacks == 0 for t in sres.tenants)
               and sres.n_degraded == 0 and sres.breaker_trips == 0,
               "no fallback, degraded decision or breaker trip")

    with _phase("7 TPU vs CPU q_values", log):
        q_tpu = np.asarray(learner._q_fn(learner.params, states))
        cpu = jax.devices("cpu")[0]
        q_cpu = np.asarray(jax.jit(lambda p, s: q_values(p, fc, s))(
            jax.device_put(learner.params, cpu),
            jax.device_put(states, cpu)))
        diff = float(np.abs(q_tpu - q_cpu).max())
        tol = Q_TOL * max(1.0, float(np.abs(q_cpu).max()))
        agree = float(np.mean(q_tpu.argmax(-1) == q_cpu.argmax(-1)))
        _say(f"q_values over {len(states)} observations: max |tpu - cpu| "
             f"{diff:.3e} (tolerance {tol:.3e}), |q| max "
             f"{float(np.abs(q_cpu).max()):.3e}, agreeing actions {agree}")
        _check(np.isfinite(q_tpu).all() and diff <= tol,
               "TPU q_values agree with the CPU")


def main() -> int:
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import use_compile_cache
    from repro.telemetry import CompileLog
    _say(f"device {dev.device_kind} x {len(devices)}; compile cache "
         f"{use_compile_cache()}")
    log = CompileLog()
    t0 = time.perf_counter()
    smoke(log)
    for name, (n, secs) in sorted(log.programs.items(),
                                  key=lambda kv: -kv[1][1]):
        _say(f"compiled {name}: {n}x, {secs:.1f}s")
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    _say(f"compile total {log.total_s():.1f}s, persistent-cache hits "
         f"{log.hits}; peak HBM {peak / 2**20:.0f} MiB; wall "
         f"{time.perf_counter() - t0:.1f}s  [smoke timings]")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
