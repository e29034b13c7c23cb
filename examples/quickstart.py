"""Quickstart: train logistic regression with R-FAST over a binary tree,
fully asynchronously, with packet loss — in ~30 lines of public API.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax.numpy as jnp

from repro.core import binary_tree, generate_schedule, run_rfast
from repro.data import make_logistic_problem

N_NODES = 7

# 1. node-local data shards (heterogeneous: label-sorted, large ς)
prob = make_logistic_problem(N_NODES, m=2800, d=64, batch=16,
                             heterogeneous=True)

# 2. two spanning-tree communication graphs W (pull) / A (push) rooted at 0
topo = binary_tree(N_NODES)
print("common roots:", topo.roots())

# 3. an asynchronous schedule: node 6 is a 4x straggler, 20% packet loss
sched = generate_schedule(
    topo, 12_000,
    compute_time=[1, 1, 1, 1, 1, 1, 4.0],
    loss_prob=0.2, latency=0.3, seed=0)
print(f"realized delay bound D={sched.D}, activation bound T={sched.T}")


# 4. run the exact Algorithm-2 recursion
def eval_fn(x, t):
    x_bar = x.mean(0)
    return {"loss": float(prob.mean_loss(x_bar)),
            "acc": float(prob.accuracy(x_bar)), "t": t}


state, metrics = run_rfast(
    topo, sched, prob.grad_fn(),
    x0=jnp.zeros((N_NODES, prob.p)), gamma=5e-3,
    eval_every=2000, eval_fn=eval_fn)

for m in metrics:
    print(f"k={m['k']:6d}  vtime={m['t']:8.1f}  "
          f"loss={m['loss']:.4f}  acc={m['acc']:.3f}")
