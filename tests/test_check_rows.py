"""``perfbench.probe.kept_rows``, the contract between the benchmark's
check and a program's step, held in tier 1: the pure-function cases of
``perfbench/tests/test_check_rows.py`` (a step of one row a sequence, a
step of two of which one or two are kept, a sequence that ends on its
first row, rows past what is wanted; a missing and a doubled position, a
step that advanced past its rows, two programs in one call and none all
raise; a chunk's note is passed over), collected here too, so that a
change to the engine's step that breaks the contract fails where every
PR's tests run. The engine that meets it with a real step of two
positions is ``tests/test_exaone_moe.py``'s."""

from perfbench.tests.test_check_rows import (  # noqa: F401
    test_a_capture_that_breaks_the_contract_raises,
    test_a_chunk_is_not_captured_and_its_note_is_passed_over,
    test_a_sequence_that_ends_on_its_first_row_keeps_one,
    test_one_row_a_sequence_reads_as_the_step_count_did,
    test_rows_past_what_is_wanted_are_passed_over,
    test_two_rows_a_step_keep_what_each_sequence_advanced_by)
