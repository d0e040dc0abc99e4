import os
import sys

# tests run against src/ without installation
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# NOTE: do NOT set --xla_force_host_platform_device_count here — smoke
# tests and benches must see the real single device; only the dry-run
# subprocess uses 512 placeholder devices.
