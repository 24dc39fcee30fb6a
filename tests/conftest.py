"""Shared fixtures for the tier-1 suite."""
import pytest


@pytest.fixture(autouse=True)
def _reset_dispatch_count():
    """Zero ops.dispatch_count() around every test.

    The counter is process-global, so without this a test that asserts
    launch counts would see whatever the previously-run module left
    behind — pass/fail would depend on collection order.
    """
    from repro.kernels import ops

    ops.reset_dispatch_count()
    yield
    ops.reset_dispatch_count()


class CompileCounter:
    """XLA compiles and lowerings while ``on``, by jax.monitoring."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        self.on = False
        self.compiles = self.lowerings = 0

    def __call__(self, event, _secs, **_kw):
        if self.on:
            self.compiles += event == self.COMPILE
            self.lowerings += event == self.LOWER


@pytest.fixture
def compile_counter():
    import jax

    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    yield counter
    jax.monitoring.unregister_event_duration_listener(counter)


@pytest.fixture
def poison_frontend(monkeypatch):
    """-> poison(workload, error): from then on the workload's front end
    raises ``error`` on every tile that holds the codepoint ``poison``
    returns, whichever of its front ends it runs."""
    import numpy as np

    marker = "\u2620"

    def poison(workload, error):
        host, device = workload._frontend_host, workload._launch_frontend

        def host_(docs):
            if any(marker in d for d in docs):
                raise error("poison document")
            return host(docs)

        def device_(tile):
            if (np.asarray(tile) == ord(marker)).any():
                raise error("poison document")
            return device(tile)

        monkeypatch.setattr(workload, "_frontend_host", host_)
        monkeypatch.setattr(workload, "_launch_frontend", device_)
        return marker

    return poison
