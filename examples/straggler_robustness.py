"""Straggler robustness head-to-head (the paper's Table II story):
R-FAST vs Ring-AllReduce vs OSGP with one 4x-slow node — every
algorithm on the SAME NetworkScenario virtual clock (the runnable doc
for DESIGN.md §7).

    PYTHONPATH=src python examples/straggler_robustness.py
"""
import jax.numpy as jnp
import numpy as np

from repro.core import (binary_tree, directed_ring, generate_schedule,
                        get_scenario, run_rfast)
from repro.core.baselines import run_osgp, run_ring_allreduce
from repro.data import make_logistic_problem

n, target = 8, 0.35
scenario = get_scenario("straggler", n)   # last node 4x slow, latency 0.3
prob = make_logistic_problem(n, m=2800, d=64, batch=16, heterogeneous=True)
gfn = prob.grad_fn()


def eval_fn(x, t):
    xb = jnp.asarray(x)
    if xb.ndim == 2:
        xb = xb.mean(0)
    return {"loss": float(prob.mean_loss(xb)), "t": t}


def t_to(ms):
    return next((m["t"] for m in ms if m["loss"] <= target), float("inf"))


K = 9600
# one scenario realization drives R-FAST's schedule...
sched = generate_schedule(binary_tree(n), K, scenario=scenario)
_, ms = run_rfast(binary_tree(n), sched, gfn, jnp.zeros((n, prob.p)),
                  gamma=5e-3, eval_every=300, eval_fn=eval_fn)
t_rfast = t_to(ms)
print(f"R-FAST         : vtime-to-loss={t_rfast:8.1f}  (1.00x)")

# ... the same scenario's barrier clock prices the synchronous rounds ...
rounds = K // n
_, ms = run_ring_allreduce(n, gfn, jnp.zeros(prob.p), 5e-3, rounds,
                           scenario=scenario, eval_fn=eval_fn,
                           eval_every=30)
t_ring = t_to(ms)
print(f"Ring-AllReduce : vtime-to-loss={t_ring:8.1f}  "
      f"({t_ring/t_rfast:.2f}x slower — pays the straggler every barrier)")

# ... and the same scenario's event clock drives OSGP's pushes.
_, ms = run_osgp(directed_ring(n), gfn, jnp.zeros((n, prob.p)), 5e-3, K,
                 scenario=scenario, eval_fn=eval_fn, eval_every=300)
t_osgp = t_to(ms)
print(f"OSGP           : vtime-to-loss={t_osgp:8.1f}  "
      f"({t_osgp/t_rfast:.2f}x)")
