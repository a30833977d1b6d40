"""Example drivers, family `qm`: see tests/_examples.py."""

from tests._examples import _run, check_example, family


@family("qm")
def test_example(script, args, expected):
    check_example(script, args, expected)


def test_qm7x_train_then_inference():
    """train.py writes the checkpoint; inference.py reloads it through
    run_prediction (the reference qm7x_mlip_inference.py workflow)."""
    r = _run("examples/qm7x/train.py", "--frames", "60", "--epochs", "2")
    assert r.returncode == 0, r.stderr[-2000:]
    r = _run("examples/qm7x/inference.py", "--frames", "40", "--epochs", "2")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "inference error" in r.stdout
