"""Example drivers, family `forces`: see tests/_examples.py."""

from tests._examples import check_example, family


@family("forces")
def test_example(script, args, expected):
    check_example(script, args, expected)
