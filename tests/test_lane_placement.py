"""Where a round's decode sequences sit among its lanes
(`model_runner.place_lanes`): the sequences on one leading page take row
blocks of their own, so that `shared_runs`, which finds a run only where
every live lane of a row block holds the same pages, finds one wherever
two sequences share."""
import numpy as np
import pytest

from production_stack_tpu.engine.model_runner import (
    RAGGED_TQ, place_lanes, seat_least, shared_runs,
)

TQ = RAGGED_TQ


def _pages(spec: str) -> list[int]:
    """'AABA.' -> first pages: a letter is a prefix (its page 100 +
    the letter's place in the alphabet), a '.' a sequence alone on a
    page of its own."""
    own = iter(range(1000, 2000))
    return [next(own) if c == "." else 100 + ord(c) - ord("A")
            for c in spec]


CASES = {
    # name: (first pages, lanes b, the expected lane of each sequence)
    "two_groups_two_blocks_idle_between":
        ("ABAB", 16, [0, 8, 1, 9]),
    "the_larger_group_first":
        ("BAAAB", 16, [8, 0, 1, 2, 9]),
    "a_tie_goes_to_the_first_seen":
        ("BABA", 16, [0, 8, 1, 9]),
    "four_groups_four_blocks":
        ("ABCDABCDA", 32, [0, 8, 16, 24, 1, 9, 17, 25, 2]),
    "singles_fill_the_blocks_left_in_order":
        (".A.A.", 24, [8, 0, 9, 1, 10]),
    # three groups and a single over two blocks: A takes block 0, then
    # B's block would leave C and the single no lane: B, C and the
    # single fill block 1 in the order given
    "more_groups_than_blocks":
        ("ABCABC.", 16, [0, 8, 9, 1, 10, 11, 12]),
    # 9 of A take two blocks (8 + 1), B the third
    "a_group_of_more_than_eight":
        ("AAAAAAAAABB", 24, [0, 1, 2, 3, 4, 5, 6, 7, 8, 16, 17]),
    # A's two blocks would leave B's two no lane, B's block leaves A's
    # nine 8: nobody moves
    "no_room_for_blocks_of_their_own":
        ("AAAAAAAAABB", 16, list(range(11))),
    # the large group cannot have its blocks, the small one after it can
    "a_smaller_group_after_one_that_does_not_fit":
        ("A" * 9 + "B" * 8 + "...", 24,
         [8, 9, 10, 11, 12, 13, 14, 15, 16] + list(range(8))
         + [17, 18, 19]),
    "one_group_of_everybody_is_the_identity":
        ("AAAAA", 16, [0, 1, 2, 3, 4]),
    "no_group_is_the_identity":
        (".....", 16, [0, 1, 2, 3, 4]),
    "one_sequence": ("A", 16, [0]),
    "every_lane_taken_by_one_prefix":
        ("A" * 16, 16, list(range(16))),
    "every_lane_taken_by_two_prefixes":
        ("AB" * 8, 16, [i // 2 + 8 * (i % 2) for i in range(16)]),
    # fewer lanes than a row block: one block, nothing to take
    "a_round_of_four_lanes": ("ABAB", 4, [0, 1, 2, 3]),
    # 12 lanes: block 1 has four
    "a_last_block_that_is_not_whole":
        ("AABBB", 12, [8, 9, 0, 1, 2]),
}


# where a shared pass costs more than two walks (`seat_least`): a pair
# stays where it arrived, three sit together
LEAST = {"a_pair_is_no_group_at_three": 3, "three_are_a_group_at_three": 3}
CASES.update({
    "a_pair_is_no_group_at_three": ("ABA.B", 16, [0, 1, 2, 3, 4]),
    "three_are_a_group_at_three": ("ABABA", 16, [0, 8, 1, 9, 2]),
})


def test_seat_least():
    assert (seat_least(False), seat_least(True)) == (2, 3)


@pytest.mark.parametrize("name", sorted(CASES))
def test_place_lanes(name):
    spec, b, want = CASES[name]
    pages = _pages(spec)
    least = LEAST.get(name, 2)
    lanes = place_lanes(pages, b, least)
    assert lanes.dtype == np.int32 and lanes.tolist() == want
    # nobody is left out, no lane is taken twice, none beyond the last
    assert len(set(lanes.tolist())) == len(pages)
    assert 0 <= lanes.min() and lanes.max() < b
    # the same sequences in the same order: the same map
    assert place_lanes(list(pages), b, least).tolist() == want

    # `shared_runs` on the tables as placed: a run in every row block
    # that holds two lanes of ONE group and nobody else, 0 elsewhere
    block, n_pages = 16, 6
    tables = np.zeros((b, n_pages), np.int32)
    ctx = np.zeros((b,), np.int32)
    own = iter(range(5000, 9000))
    for lane, page in zip(lanes.tolist(), pages):
        # four shared pages of the prefix, then two of the lane's own
        tables[lane, :4] = [page * 10 + j for j in range(4)]
        tables[lane, 4:] = [next(own), next(own)]
        ctx[lane] = 4 * block + 9
    runs = shared_runs(tables, ctx, block)
    for blk in range(-(-b // TQ)):
        held = [p for lane, p in zip(lanes.tolist(), pages)
                if lane // TQ == blk]
        shares = len(held) >= 2 and len(set(held)) == 1
        assert runs[blk, 0] == (4 * block if shares else 0), (blk, held)
        if shares:
            assert runs[blk, 1] == min(
                lane for lane in lanes.tolist() if lane // TQ == blk)
