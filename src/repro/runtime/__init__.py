from repro.runtime.fault import FaultConfig, retry_step
from repro.runtime.elastic import reshard_engine, replan_split

__all__ = ["FaultConfig", "retry_step", "reshard_engine", "replan_split"]
