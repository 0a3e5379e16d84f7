"""Set-up for every test directory, made before JAX starts.

* Four host CPU devices: the benchmark's 4-chip cell
  (``graph500-18-mesh4.pagerank``) runs at test size on a ``machines``
  mesh of four devices, in the test's own process.  Every other test
  runs on the first device, as it would with one.
* The benchmark tests' shrunk sizes (``TINY`` of ``tests/bench/
  conftest.py``) gain that cell's configuration: a scale-9 graph, on the
  same 1 + 3 machines as at full size.
"""
import os

_DEVICES = "--xla_force_host_platform_device_count"
if _DEVICES not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = " ".join(
        filter(None, [os.environ.get("XLA_FLAGS"), f"{_DEVICES}=4"]))

_TINY_MESH4 = {"graph500-18-mesh4": {"scale": 9}}


def pytest_plugin_registered(plugin):
    tiny = getattr(plugin, "TINY", None)
    if isinstance(tiny, dict):
        for name, sizes in _TINY_MESH4.items():
            tiny.setdefault(name, sizes)
