"""Test configuration: force an 8-device virtual CPU mesh.

Mirrors the reference's local-mode Spark testing strategy
(/root/reference/deeplearning4j-scaleout/spark/dl4j-spark/src/test/java/org/deeplearning4j/spark/BaseSparkTest.java:90
`.setMaster("local[n]")`): distributed logic runs multi-"device" in one process.

The suite compiles thousands of small programs once each: persisting them
(``util/compile_cache.py``, which the CLI entry points some tests call
in-process turn on) would only cost disk writes, so the persistent compile
cache is held off for the test process.
"""
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
