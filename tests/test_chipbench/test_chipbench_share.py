"""What test_chipbench_spec.py's
`test_an_accepted_cell_may_not_go_and_additions_keep_the_chip_share` holds,
at ANY number of cells. That test appends ONE cell on four chips to the
repository's cells and expects the refusal, and the contract allows a
quarter of the cells, rounded down: from seven cells on (7 + 1 = 8, two on
four chips) its last expectation is not the contract's. These cases append
four-chip cells until the quarter is passed, so the seventh cell and every
later one find the same three things held: an accepted cell may not go, a
further one-chip cell is legal, and the four-chip share is refused exactly
where the contract says.
"""
import copy
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import chipbench_toy as toy  # noqa: E402

sys.path.insert(0, toy.REPO)

from chipbench.harness import contract  # noqa: E402


def with_more_cells(spec, count, chips):
    """`spec` and `count` copies of one of its ONE-chip cells appended
    under new names and traffic, each on `chips` chips, listed wherever the
    copied cell is."""
    more = copy.deepcopy(spec)
    first = [w for w in more['workloads'] if w['chips'] == 1][0]
    for i in range(count):
        name = 'appended_%d' % i
        more['workloads'].append(dict(first, name=name, chips=chips,
                                      traffic='another_%d' % i))
        for m in more['end_to_end'] + more['per_layer']:
            if first['name'] in m.get('workloads', ()):
                m['workloads'].append(name)
    return more


@pytest.mark.parametrize('name', sorted(contract.HELD_CELLS))
def test_an_accepted_cell_may_not_go(name):
    gone = copy.deepcopy(toy.repo_spec())
    gone['workloads'] = [w for w in gone['workloads'] if w['name'] != name]
    for m in gone['end_to_end'] + gone['per_layer']:
        if 'workloads' in m:
            m['workloads'] = [w for w in m['workloads'] if w != name]
    # its configuration goes with its last cell: the refusal is the cell's
    used = {w['config'] for w in gone['workloads']}
    gone['configs'] = [c for c in gone['configs'] if c['name'] in used]
    with pytest.raises(contract.ContractError, match='accepted cell'):
        contract.check_spec(gone)


def test_an_accepted_cell_may_not_change_its_chips():
    moved = copy.deepcopy(toy.repo_spec())
    for w in moved['workloads']:
        if w['name'] == 'tfm_s256':
            w['chips'] = 4
    with pytest.raises(contract.ContractError):
        contract.check_spec(moved)


def test_a_further_one_chip_cell_is_legal():
    contract.check_spec(with_more_cells(toy.repo_spec(), 1, chips=1))


def test_four_chip_cells_are_refused_where_the_quarter_is_passed():
    """Four-chip cells appended one by one: each is legal while the cells
    on four chips are at most a quarter of all, rounded down (and one
    always may), and the next one is refused by that rule and no other."""
    spec = toy.repo_spec()
    n = len(spec['workloads'])
    have = sum(w['chips'] == 4 for w in spec['workloads'])
    refused = None
    for count in range(1, 24 - n + 1):
        more = with_more_cells(spec, count, chips=4)
        if have + count <= max(1, (n + count) // 4):
            contract.check_spec(more)
            continue
        with pytest.raises(contract.ContractError, match='four chips'):
            contract.check_spec(more)
        refused = count
        break
    assert refused is not None
    # the repository's own next cell: what this PR's successor may add
    assert refused == 1 or (have + refused - 1) * 4 <= n + refused - 1
