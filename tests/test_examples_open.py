"""Example drivers, family `open`: see tests/_examples.py."""

from tests._examples import check_example, family


@family("open")
def test_example(script, args, expected):
    check_example(script, args, expected)
