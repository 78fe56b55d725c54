"""CLI argument plumbing (engine/arg_utils.py): chunked prefill is on
unless --disable-chunked-prefill says otherwise, and the --replica-role
flag round-trips into SchedulerConfig."""
import argparse

import pytest

from intellillm_tpu.engine.arg_utils import EngineArgs


def _parse(argv):
    parser = EngineArgs.add_cli_args(argparse.ArgumentParser())
    return parser.parse_args(argv)


@pytest.mark.parametrize("argv,chunked", [([], True),
                                          (["--disable-chunked-prefill"],
                                           False)])
def test_chunked_prefill_is_on_unless_disabled(argv, chunked):
    engine_args = EngineArgs.from_cli_args(_parse(["--model", "m"] + argv))
    assert (engine_args.enable_chunked_prefill
            and not engine_args.disable_chunked_prefill) is chunked


@pytest.mark.parametrize("role", ["mixed", "prefill", "decode"])
def test_replica_role_round_trips(role):
    args = _parse(["--model", "m", "--replica-role", role])
    engine_args = EngineArgs.from_cli_args(args)
    assert engine_args.replica_role == role


def test_replica_role_rejects_unknown():
    with pytest.raises(SystemExit):
        _parse(["--model", "m", "--replica-role", "draft"])
    from intellillm_tpu.config import SchedulerConfig
    with pytest.raises(ValueError, match="replica_role"):
        SchedulerConfig(max_num_batched_tokens=512, max_num_seqs=4,
                        max_model_len=128, max_paddings=512,
                        replica_role="draft")
