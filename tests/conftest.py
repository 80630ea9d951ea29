# NOTE: no XLA_FLAGS here on purpose — smoke tests and benches must see
# ONE device; only launch/dryrun.py (and subprocess tests) force 512/8
# host devices, each in its own process.

import collections

import jax
import pytest


@pytest.fixture
def events():
    """``jax.monitoring`` events recorded while the test runs."""
    seen = collections.Counter()
    live = [True]

    def listen(event, *_, **__):
        if live[0]:
            seen[event] += 1

    jax.monitoring.register_event_listener(listen)
    yield seen
    live[0] = False
