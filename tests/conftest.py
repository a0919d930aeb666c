"""Test configuration: force an 8-device virtual CPU platform.

Multi-chip TPU hardware is not available in CI; sharding correctness is
validated on a virtual 8-device CPU mesh exactly as the driver's
``dryrun_multichip`` does.  The suite ALWAYS runs on the CPU
(``JAX_PLATFORMS=cpu``, set here before jax is imported so that
subprocesses the tests spawn inherit it); the accelerator is exercised by
``chip_smoke.py``, one process per chip.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# XLA:CPU runs every participant of an in-process collective on a thread of
# the PjRt client's pool and holds it for as long as the rendezvous waits.
# The pool has max(cores, devices) threads unless PJRT_NPROC says otherwise,
# so on a box with 8 cores the 8 virtual devices leave no slack: reading a
# mesh-served engine's state (`doc_state`'s `x[slot]`, one 8-participant
# all-reduce program per leaf, dispatched back to back) left seven
# participants waiting for an eighth that never got a thread, and XLA
# aborted the process 40 s later.  Two programs' worth of threads (the one in
# the rendezvous and the next, whose participants take theirs before the first
# is complete) is the least that passed; more only oversubscribes the workers.
os.environ.setdefault("PJRT_NPROC", "16")

# Persistent XLA compile cache: the suite is compile-dominated on small CI
# boxes (hundreds of unique engine/kernel geometries, each a multi-second
# XLA compile), and every pytest process recompiles from scratch.  Caching
# compiled executables on disk makes reruns bounded by actual test work.
# utils/compile_cache.py decides the directory (JAX_COMPILATION_CACHE_DIR
# when set, else the fixed gitignored <checkout>/.jax_compile_cache).
from fluidframework_tpu.utils import compile_cache  # noqa: E402

compile_cache.enable()

import pytest  # noqa: E402

# Modules whose every test triggers JAX kernel compilation (the expensive
# lane).  Everything else is host-plane Python and forms the <2-min smoke
# lane (`pytest -m "not device"`).
_DEVICE_MODULES = {
    "test_cohort_compact",
    "test_columnar_ingest",
    "test_doc_batch_engine",
    "test_fleet_consumer",
    "test_kernel_channel",
    "test_long_doc",
    "test_matrix_kernel",
    "test_megastep",
    "test_mergetree_kernel",
    "test_mesh_conformance",
    "test_multidevice",
    "test_native_ingest",
    "test_obliterate",
    "test_overflow_recovery",
    "test_pallas_kernels",
    "test_scribe",
    "test_segment_parallel",
    "test_shared_map",
    "test_tree_batch_engine",
    "test_tree_kernel",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.module.__name__ in _DEVICE_MODULES:
            item.add_marker(pytest.mark.device)
            continue
        # The kernel leg of dual-backend tests compiles the merge-tree
        # kernel; the oracle leg stays in the fast lane.
        callspec = getattr(item, "callspec", None)
        if callspec is not None and callspec.params.get("string_backend") == "kernel":
            item.add_marker(pytest.mark.device)


@pytest.fixture(params=["oracle", "kernel"])
def string_backend(request):
    """Run a test once on the Python oracle and once with the TPU kernel
    behind the channel boundary (the north star's plugin gate,
    ref datastore-definitions/src/channel.ts:294).  Modules opt in with
    ``pytestmark = pytest.mark.usefixtures("string_backend")``."""
    if request.param == "kernel":
        from fluidframework_tpu.dds import channels
        from fluidframework_tpu.dds.kernel_backend import KernelMergeTree

        channels.set_string_backend_factory(
            lambda: KernelMergeTree(
                max_segments=1024,
                remove_slots=6,
                prop_slots=4,
                text_capacity=16384,
                max_insert_len=16,
                ob_slots=16,
            )
        )
        yield "kernel"
        channels.set_string_backend_factory(None)
    else:
        yield "oracle"
