"""Serving demo: continuous batching with load-driven autoscaling.

A Poisson arrival trace is served by the continuous-batching engine over
the paged KV backend; the engine publishes queue depth / latency /
occupancy into the registry KV, and the cluster's autoscaling policy grows
the node set while the backlog is deep, then shrinks it as the queue
drains. The greedy pass verifies tokens against the one-shot serve_batch
baseline; the second pass serves the same trace with seeded nucleus
sampling under EDF admission and verifies the sampled streams are
bit-identical across two different engine shapes (the serving API v2
lane-placement-invariance contract).

    PYTHONPATH=src python examples/serve_demo.py
"""
import os
import subprocess
import sys

BASE = [sys.executable, "-m", "repro.launch.serve", "--arch", "paper-demo",
        "--smoke", "--trace", "poisson", "--verify"]
SAMPLED = ["--temperature", "0.8", "--top-k", "40", "--top-p", "0.95",
           "--sched", "edf", "--deadline", "2.0"]
# the environment passes through unchanged: the children run on whatever
# backend JAX finds (set JAX_PLATFORMS=cpu to keep them on the CPU)
ENV = {**os.environ, "PYTHONPATH": "src"}

if __name__ == "__main__":
    rc = subprocess.call(BASE, env=ENV)
    sys.exit(rc or subprocess.call(BASE + SAMPLED, env=ENV))
