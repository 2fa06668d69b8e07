"""The edge of the quiet loop, the law's exactness (tests/test_loop_edge.py
has what the law is, tests/loop_edge_laws.py its body): the eager
routing path's and ``route_cap``'s cases, a file of their own because
every case compiles an engine's five programs."""

from loop_edge_laws import carried_horizon_law, law_cases


@law_cases("eager", "lazy")
def test_the_carried_horizon_is_the_states_and_the_drivers_agree(
        name, faulted):
    carried_horizon_law(name, faulted)
