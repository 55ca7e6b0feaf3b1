import os
import sys

# NOTE: no XLA_FLAGS here on purpose — unit/smoke tests must see the real
# (single) host device. Multi-device integration tests live in
# tests/multidevice/* and are launched as subprocesses with their own
# --xla_force_host_platform_device_count (see test_multidevice.py).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
