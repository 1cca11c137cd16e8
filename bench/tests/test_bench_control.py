"""The control of `correct` at a size a test run holds: the reference
computed in bfloat16 in the program's place fails the limit that the
program passes (`bench/control.py` reads the same at the cells' own size
on the chip)."""

import time

import pytest

from tiny_cell import tiny_files

SEED = 2**31 + 99


@pytest.mark.parametrize("config_name, mix_name",
                         [("danube-1.8b-s50w8", "chat-0.68rps"),
                          ("danube-1.8b-dense", "chat-0.48rps")])
def test_bfloat16_control_fails_the_limit_the_program_passes(config_name,
                                                             mix_name):
    import jax
    from bench import cell
    jax.config.update("jax_enable_compilation_cache", False)
    config, mix = tiny_files(config_name, mix_name)
    r = cell.run(config, mix, SEED, 2.0, False, time.perf_counter(), [], [],
                 control=True)
    check = r["checks"]["mean_logit_gap"]
    assert r["correct"] is True and check["value"] <= check["limit"]
    assert r["control"]["correct"] is False
    assert r["control"]["checks"]["mean_logit_gap"]["value"] > check["limit"]
