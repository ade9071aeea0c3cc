from repro.serve.chaos import ChaosConfig
from repro.serve.engine import ServeEngine, ServeConfig, SpecConfig
from repro.serve.http import FrontDoor, HttpConfig
from repro.serve.policy import (Overloaded, PriorityClass, RateLimited,
                                SloConfig, SloMonitor, TenantPolicy,
                                TenantSpec)
from repro.serve.request import Request, SubmitRequest
from repro.serve.sampling import sample_token, spec_accept
from repro.serve.scheduler import BlockAllocator, ContinuousScheduler
from repro.serve.trace import (spec_accept_len, tenant_report, token_prices,
                               trace_energy, useful_tokens)

__all__ = [
    "BlockAllocator",
    "ChaosConfig",
    "ContinuousScheduler",
    "FrontDoor",
    "HttpConfig",
    "Overloaded",
    "PriorityClass",
    "RateLimited",
    "Request",
    "ServeConfig",
    "ServeEngine",
    "SloConfig",
    "SloMonitor",
    "SpecConfig",
    "SubmitRequest",
    "TenantPolicy",
    "TenantSpec",
    "sample_token",
    "spec_accept",
    "spec_accept_len",
    "tenant_report",
    "token_prices",
    "trace_energy",
    "useful_tokens",
]
