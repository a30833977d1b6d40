"""Example drivers, family `sc26`: see tests/_examples.py."""

from tests._examples import check_example, family


@family("sc26")
def test_example(script, args, expected):
    check_example(script, args, expected)
