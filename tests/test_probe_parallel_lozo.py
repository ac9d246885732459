"""Probe-parallel parity for LoZO and SubZO, run by
tests/_fake_mesh.py run_probe_parallel_parity on 8 fake host devices."""
import pytest

from _fake_mesh import run_probe_parallel_parity


@pytest.mark.slow
@pytest.mark.parametrize("method", ("lozo", "lozo_m", "subzo"))
def test_probe_parallel_parity(tmp_path, method):
    run_probe_parallel_parity(tmp_path, method)
