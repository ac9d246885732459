"""Probe-parallel parity for the TeZO methods, run by
tests/_fake_mesh.py run_probe_parallel_parity on 8 fake host devices;
tezo_adam also runs the uneven q=3 split."""
import pytest

from _fake_mesh import run_probe_parallel_parity


@pytest.mark.slow
@pytest.mark.parametrize("method", ("tezo", "tezo_m", "tezo_adam"))
def test_probe_parallel_parity(tmp_path, method):
    run_probe_parallel_parity(tmp_path, method)
