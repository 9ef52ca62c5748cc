"""Test harness config: force a virtual 8-device CPU mesh BEFORE jax import.

Multi-node-without-a-cluster is a first-class capability (the reference's
single-node docker collapse, README.md:51-58); here it's a CPU-simulated
device mesh, per SURVEY.md §4.
"""

import os
import sys

# Tests run on an 8-device virtual CPU mesh whatever the machine holds
# (backends are lazy; the first jax.devices() call happens inside the
# tests). The env var covers any subprocesses tests may spawn.
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
