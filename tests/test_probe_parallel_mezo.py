"""Probe-parallel parity for the MeZO methods, run by
tests/_fake_mesh.py run_probe_parallel_parity on 8 fake host devices."""
import pytest

from _fake_mesh import run_probe_parallel_parity


@pytest.mark.slow
@pytest.mark.parametrize("method", ("mezo", "mezo_m", "mezo_adam"))
def test_probe_parallel_parity(tmp_path, method):
    run_probe_parallel_parity(tmp_path, method)
