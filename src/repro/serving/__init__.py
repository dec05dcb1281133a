from repro.serving.engine import EngineConfig, ServingEngine
from repro.serving.faults import (FaultError, FaultInjector, FaultPlan,
                                  HealthMonitor, InjectedFault)
from repro.serving.request import Request, Response
from repro.serving.server import AsyncServingServer
# the engine's former fleet name, still imported by perfbench/run.py
from repro.serving.sharded import ShardedServingEngine

__all__ = ["EngineConfig", "ServingEngine", "ShardedServingEngine",
           "AsyncServingServer", "Request", "Response",
           "FaultPlan", "FaultInjector", "FaultError", "HealthMonitor",
           "InjectedFault"]
