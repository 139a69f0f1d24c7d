"""CI coverage for the driver entry points (__graft_entry__.py).

These tests run the real impl on the conftest-forced 8-device CPU mesh.
"""
import sys
import os

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import pytest

import __graft_entry__ as graft


def test_entry_compiles_and_runs():
    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (8, 10)


def test_dryrun_multichip_inprocess():
    # conftest asks for the CPU (JAX_PLATFORMS=cpu) with an 8-device
    # virtual mesh — the explicit opt-in dryrun_multichip requires
    # before it will run on anything but real chips.
    assert len(jax.devices()) >= 8
    graft.dryrun_multichip(8)


def test_dryrun_multichip_fails_rather_than_moving_to_a_virtual_mesh(
        monkeypatch):
    """More devices than are visible is an error naming the count — not
    a re-execution on a CPU mesh the caller never asked for."""
    monkeypatch.setattr(graft, "_dryrun_multichip_impl",
                        lambda n: pytest.fail("must not run"))
    with pytest.raises(RuntimeError, match="device"):
        graft.dryrun_multichip(len(jax.devices()) + 1)
