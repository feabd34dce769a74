import os
import sys

# the harness's tests run on the CPU, through the program's jnp oracles
os.environ.setdefault("REPRO_PALLAS", "ref")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))
