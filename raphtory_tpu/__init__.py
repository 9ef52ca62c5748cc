"""raphtory_tpu — a TPU-native temporal graph analytics framework.

Brand-new design with the capabilities of Raphtory (Scala/Akka era):
streaming ingestion into an append-only bitemporal store, and Pregel-style
BSP analysis over historical views/windows — re-expressed as JAX/XLA SPMD
programs over immutable CSR snapshots sharded across a TPU mesh.
"""

import os as _os

# Vertex ids and event times are int64; enable x64 before any jax use.
# Engine/device code keeps compute dtypes explicit (f32/bf16/i32) so the MXU
# path is unaffected. Opt out with RAPHTORY_TPU_X64=0.
if _os.environ.get("RAPHTORY_TPU_X64", "1") != "0":
    import jax as _jax

    _jax.config.update("jax_enable_x64", True)

# RTPU_SANITIZE=1 installs the lock sanitizer before any package module
# creates its locks (cycle + held-across-device_put findings land in the
# flight recorder). Disabled: this costs one env read and imports nothing.
if _os.environ.get("RTPU_SANITIZE", "0") not in ("", "0", "false"):
    from .analysis.sanitizer import maybe_install_from_env as _mi

    _mi()

# Persistent XLA compilation cache, wired before the first compile: where
# JAX_COMPILATION_CACHE_DIR says when it is set, else one fixed path in
# the checkout — none in a CPU-pinned process
# (utils/config.configure_compile_cache).
from .utils.config import configure_compile_cache as _ccc

_ccc()

from .core.events import EventLog
from .core.snapshot import GraphView, build_view
from .engine import bsp
from .engine.program import Context, Edges, VertexProgram

__version__ = "0.1.0"

__all__ = [
    "EventLog",
    "GraphView",
    "build_view",
    "bsp",
    "VertexProgram",
    "Context",
    "Edges",
    "__version__",
]
