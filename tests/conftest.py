# NOTE: do NOT set --xla_force_host_platform_device_count here — smoke
# tests and benches must see the real device count; only launch/dryrun.py
# (and the subprocess tests that exec it) force placeholder devices.
import contextlib
import functools
import os
import sys
from unittest import mock

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# The property tiers need hypothesis; environments without it fall back
# to the deterministic compat stub so those tests run instead of skip.
try:
    import hypothesis  # noqa: F401
except ImportError:
    sys.path.insert(0, os.path.dirname(__file__))
    import _hypothesis_stub
    _hypothesis_stub.install()


@pytest.fixture
def dispatch_on():
    """``dispatch_on(platform)``: a context in which every attention
    layer's dispatch (``attend``, as ``attn_apply`` and ``mla_apply`` call
    it) is told that it runs on ``platform``.  Off the TPU the kernel then
    runs in interpret mode."""
    from repro.models import attention, mla

    @contextlib.contextmanager
    def on(platform):
        steered = functools.partial(attention.attend, platform=platform)
        with mock.patch.object(attention, "attend", steered), \
                mock.patch.object(mla, "attend", steered):
            yield
    return on
