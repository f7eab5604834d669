"""Layer B: trace-driven reproduction of the paper's SST evaluation."""
from repro.simx import engine, time, trace  # noqa: F401
