"""Example drivers, family `giant_halo_multidataset`: see tests/_examples.py."""

from tests._examples import check_example, family


@family("giant_halo_multidataset")
def test_example(script, args, expected):
    check_example(script, args, expected)
