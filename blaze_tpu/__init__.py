"""blaze-tpu: a TPU-native Spark SQL acceleration framework.

A brand-new framework with the capabilities of the Blaze Spark accelerator
(reference: /root/reference, a Rust/DataFusion CPU engine): it accepts a
serialized physical-plan tree per Spark task partition and executes it on
columnar data — but the engine here is jax/XLA on TPU. Columnar batches are
device arrays with static (bucketed) shapes, operators are fused into
`jax.jit`-compiled pipelines, hash tables are replaced by sort-based
algorithms (grouping, joins), and the shuffle partitioning step can run as
collectives over a TPU ICI mesh.

Layer map (mirrors SURVEY.md §1, re-designed TPU-first):
  - plan/       plan contract (protobuf + in-memory IR) — ref: blaze-serde
  - exprs/      expression compiler pb-expr -> jax        — ref: datafusion-ext-exprs
  - columnar/   device batch model + Arrow interop        — ref: arrow-rs usage
  - ops/        physical operators                        — ref: datafusion-ext-plans
  - parallel/   device-mesh collectives (ICI shuffle)     — (TPU-native, no ref analog)
  - runtime/    per-task executor, memory, metrics, jit   — ref: blaze/src/rt.rs
  - native/     C++ layer: wire serde, JNI bridge         — ref: blaze-jni-bridge
  - spark/      Spark-side planner logic                  — ref: spark-extension
"""

__version__ = "0.1.0"

# Spark semantics need real int64/float64 columns; jax disables 64-bit by
# default. Must run before any jax array is created anywhere in the package.
import jax

jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: every (operator, key mix, capacity)
# is its own program and a query engine re-runs the same plan shapes
# across processes — AQE re-plans, retried tasks, repeated analyst
# queries. The disk cache turns each shape's compile into a once-ever
# cost. The rule (README "TPU-deployment design notes"):
#   * JAX_COMPILATION_CACHE_DIR set: jax reads it itself; nothing here
#     sets, moves or extends the directory.
#   * unset: <checkout>/.jax_cache (git-ignored) — a fixed path, because
#     the path is part of the cache key.
#   * unset and JAX_PLATFORMS=cpu (the test platform): uncached. XLA:CPU
#     artifacts bake the compiling machine's features and CPU compiles are
#     cheap. Decided from the env STRING: importing this package must not
#     initialize a backend (on the chip that would take the chip — a
#     launcher, pytest collection or a tools/ reader imports it too).
import os as _os

_DEFAULT_CACHE_DIR = _os.path.join(_os.path.dirname(_os.path.dirname(
    _os.path.abspath(__file__))), ".jax_cache")
_cpu_only = _os.environ.get("JAX_PLATFORMS", "").strip() == "cpu"
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR") and not _cpu_only:
    jax.config.update("jax_compilation_cache_dir", _DEFAULT_CACHE_DIR)
if not _cpu_only:
    # cache EVERY program: the engine's many small per-shape programs
    # (slices, concats, probes) add up to tens of seconds per cold query
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

from blaze_tpu.config import BlazeConf, conf

__all__ = ["BlazeConf", "conf", "__version__"]
