"""The control: the plain reference computed one precision down
(bfloat16 for the configurations' float32), put in the program's place on
the frames a run compares. It has to come out not correct against the
limits, for every cell; the program, on the same frames, correct."""
import pytest

from bench import readings

from _tiny import tiny


@pytest.mark.parametrize("workload",
                         ["hd-batch-mag", "cam1080-moving", "cam1080-noisy"])
def test_control_fails_the_limits(workload):
    rows = readings.readings(workload, [2**31 + 3, 17], 0.3, control=True,
                             require_tpu=False, overrides=tiny(workload))
    for seed, prog_rows, prog_ok, ctrl_rows, ctrl_ok in rows:
        assert prog_ok is True, (seed, prog_rows)
        assert ctrl_ok is False, (seed, ctrl_rows)
