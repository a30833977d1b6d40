"""Example drivers, family `molecules`: see tests/_examples.py."""

from tests._examples import check_example, family


@family("molecules")
def test_example(script, args, expected):
    check_example(script, args, expected)
