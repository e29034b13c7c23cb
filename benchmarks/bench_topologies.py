"""Fig. 4a: R-FAST convergence over five topologies (7 nodes), plus
simulator-engine throughput rows (wavefront vs event-serial).

The ``topology/*`` rows reproduce the paper figure (one full training run
per topology; us_per_call = wall/K of the whole run, compile included —
the end-to-end number a user sees).  The ``sim/*`` rows isolate the
engine hot loop: warmed, median-of-k timing of the compiled scan on the
same realized schedule, one row per execution mode, so the
wavefront-vs-snapshot speedup is recorded per scale."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import generate_schedule, get_topology
from repro.core.plan import build_comm_plan
from repro.core.schedule import build_wavefront_plan
from repro.core.simulator import (init_state, pack_state, rfast_scan,
                                  rfast_wavefront_scan, wave_inputs)
from .common import csv_row, logistic_setup, measure_us, run_rfast_logistic

TOPOLOGIES = ["binary_tree", "line", "directed_ring", "exponential",
              "mesh2d"]

# (n, d, m, K-divisor) per engine-throughput scale; n=31 is where the
# snapshot engine's O((n+E)·p) history traffic dominates its event cost
ENGINE_SCALES = [(7, 64, 2800, 1), (31, 256, 8680, 2)]


def _engine_rows(name: str, K: int) -> list[str]:
    rows = []
    for n, d, m, div in ENGINE_SCALES:
        Ks = max(500, K // div)
        prob = logistic_setup(n, d=d, m=m)
        gfn = prob.grad_fn()
        topo = get_topology(name, n)
        sched = generate_schedule(topo, Ks, latency=0.3, seed=0)
        plan = build_comm_plan(topo)
        H = int(sched.D) + 2
        key = jax.random.PRNGKey(0)
        step_keys = jax.random.split(key, Ks)
        state = init_state(plan, jnp.zeros((n, prob.p), jnp.float32),
                           gfn, key, H)

        wf = build_wavefront_plan(sched, plan, H)
        waves = wave_inputs(wf, step_keys)
        packed = pack_state(state)
        runner = rfast_wavefront_scan(plan, gfn, 5e-3, donate=False,
                                      p_real=prob.p)
        us_wave = measure_us(runner, packed, waves, reps=3) / Ks

        # same schedule through the fused-grid commit (dispatch-resolved:
        # compiled on TPU, the jnp emulation twin on CPU) — the maxerr
        # keeps the grid path honest on real engine traffic
        runner_p = rfast_wavefront_scan(plan, gfn, 5e-3, donate=False,
                                        impl="pallas", p_real=prob.p)
        us_wave_p = measure_us(runner_p, packed, waves, reps=3) / Ks
        werr = max(float(jnp.abs(a - b).max()) for a, b in
                   zip(runner(packed, waves), runner_p(packed, waves)))

        chunk = rfast_scan(plan, gfn, 5e-3, H, donate=False)
        agent = jnp.asarray(sched.agent)
        sv = jnp.asarray(sched.stamp_v)
        sr = jnp.asarray(sched.stamp_rho)
        us_event = measure_us(chunk, state, agent, sv, sr, step_keys,
                              reps=3) / Ks

        rows.append(csv_row(
            f"sim/{name}_n{n}_wavefront", us_wave,
            f"speedup_vs_event={us_event / us_wave:.2f}x;"
            f"B={wf.width};waves={wf.n_waves};K={Ks}"))
        rows.append(csv_row(
            f"sim/{name}_n{n}_wavefront_pallas", us_wave_p,
            f"ratio_vs_jnp={us_wave_p / us_wave:.2f}x;"
            f"maxerr_vs_jnp={werr:.1e};B={wf.width};K={Ks}"))
        rows.append(csv_row(
            f"sim/{name}_n{n}_event", us_event,
            f"mode=event_serial_snapshot;K={Ks}"))
    return rows


def run(K: int = 12_000, n: int = 7) -> list[str]:
    prob = logistic_setup(n)
    rows = []
    for name in TOPOLOGIES:
        state, metrics, wall = run_rfast_logistic(prob, name, K)
        final = metrics[-1]
        rows.append(csv_row(
            f"topology/{name}", wall / K * 1e6,
            f"loss={final['loss']:.4f};acc={final['acc']:.3f}"))
    rows.extend(_engine_rows("binary_tree", K))
    return rows


if __name__ == "__main__":
    print("\n".join(run()))
