"""CPU tests of the harness. They never reach for a TPU: JAX is held to the
CPU before anything imports it."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
