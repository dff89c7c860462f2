"""The control (the reference at three bf16 passes in the program's
place) fails every configuration's check, at a size a test holds."""
import json

import pytest

from chipbench import control, harness

CONFIGS = sorted((harness.HERE / "configs").glob("*.json"))


@pytest.mark.parametrize("seed", (1, 2 ** 31 + 3, 2 ** 40 + 9))
@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_control_fails_the_check(path, seed):
    cfg = dict(json.loads(path.read_text()), n=1024, ref_block=384)
    got = control.readings(cfg, seed, tile=128)
    assert set(got) == set(cfg["limits"])
    assert any(not v <= cfg["limits"][k] for k, v in got.items()), got
