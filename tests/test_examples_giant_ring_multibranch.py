"""Example drivers, family `giant_ring_multibranch`: see tests/_examples.py."""

from tests._examples import check_example, family


@family("giant_ring_multibranch")
def test_example(script, args, expected):
    check_example(script, args, expected)
