"""``perfbench/flightlog.py``, the readers of the step log's host pauses
and of the ordinals a step record carries (ISSUE 57), held in tier 1: the
cases of ``perfbench/tests/test_flightlog.py`` on hand-made step logs and
a ``Trace.from_json`` trace, collected here too, so that a change to the
records' fields (``dispatched``, ``fetched``, ``carried``, ``cpu_s``,
``wait_cpu_s``) or to ``tracing.host_pauses`` that breaks a reader fails
where every PR's tests run: the pairing of a decode's module event with
the records of its ordinal (and a log shifted by one step, an event lost
or one too many, records without ordinals, which must all read ``None``),
an engine that drafts, the collector's seconds and full collections in a
window, the idle gaps a pause covers, and the parent's records, which
give every reader nothing."""

from perfbench.tests.test_flightlog import (  # noqa: F401
    test_a_few_broken_pairs_do_not_take_the_rest_away,
    test_a_module_events_name,
    test_a_pairing_that_does_not_hold_reads_nothing,
    test_a_ring_that_dropped_the_windows_first_pauses_reads_nothing,
    test_a_step_that_prefilled_is_left_out_of_its_medians,
    test_an_engine_that_drafts_has_no_lead_and_fetches_its_own_decode,
    test_benchmark_json_enters_each_reader_twice,
    test_carried_is_a_share_of_the_steps_that_went_out_ahead,
    test_gc_seconds_are_clipped_to_the_window_and_full_ones_counted,
    test_long_gaps_and_what_covers_them,
    test_off_cpu_time_is_read_over_blocks_so_a_ticking_clock_says_the_same,
    test_off_cpu_time_is_what_the_wall_holds_beyond_the_cpu_outside_the_wait,
    test_only_the_traced_records_decodes_are_paired,
    test_pairing_by_ordinals_reads_the_chips_step_the_lead_and_the_lag,
    test_the_nine_readers_on_a_run_with_one_pause,
    test_the_parents_records_and_module_give_nothing,
    test_without_a_trace_the_hosts_four_are_read)
