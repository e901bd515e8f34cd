"""A device kind missing from the peak table, and a run without a TPU,
both fail."""

import pytest

from chipbench import run
from chipbench.harness import spec


def test_unknown_device_kind_is_an_error():
    with pytest.raises(spec.SpecError):
        spec.peaks("TPU v99 imaginary")
    assert spec.peaks("TPU v5 lite")["flops_per_s"] == 197e12


def test_cpu_only_run_fails_without_a_result(capsys):
    rc = run.main(["--workload", "granite.train4k.dp1", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "no TPU" in out.err


def test_too_few_chips_fails(capsys):
    from chipbench.harness import cell
    with pytest.raises(cell.NoChip):
        cell.devices_for(8, require_tpu=False)
