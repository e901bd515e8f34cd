"""The training launcher's depth cut: ``--n-layers`` replaces the depth
of the published config and nothing else."""
import dataclasses

import pytest

from repro.configs import ARCHS, get_arch
from repro.launch.train import select_model


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_n_layers_cuts_depth_only(name):
    arch = get_arch(name)
    cut = select_model(arch, n_layers=2)
    want = dataclasses.asdict(arch.model)
    got = dataclasses.asdict(cut)
    assert got.pop("n_layers") == 2
    want.pop("n_layers")
    assert got == want          # every width, head count, vocab, dtype


def test_n_layers_default_and_smoke():
    arch = get_arch("granite-3-2b")
    assert select_model(arch) is arch.model
    assert select_model(arch, smoke=True) is arch.smoke
    assert select_model(arch, smoke=True, n_layers=1).d_model \
        == arch.smoke.d_model
    with pytest.raises(ValueError, match="n_layers"):
        select_model(arch, n_layers=0)
