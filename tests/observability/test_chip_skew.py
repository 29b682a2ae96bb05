"""The chips of a four-chip cell read side by side, in tier-1: the cases of
``benchmark/tests/test_chip_skew.py`` (that directory runs in no driver
run), imported and collected here so that one text is run in both places:
``benchmark/layer_metrics/chip_skew.py``'s cut of a collective's time into
transfer and the wait for the latest chip on four device planes made by
hand, and ``tools/trace_by_scope.py``'s table of the same."""

import pytest

from benchmark.tests.test_chip_skew import (  # noqa: F401
    test_a_metric_has_its_file_its_entry_and_nothing_to_say_without_a_trace,
    test_a_step_whose_chips_hold_other_occurrences_publishes_nothing,
    test_the_operators_table_is_the_readers,
    test_transfer_and_wait_of_known_arrivals_to_the_nanosecond,
    test_wait_and_transfer_add_up_to_the_collectives_time,
    test_what_an_empty_event_hid_is_taken_back,
)

pytestmark = pytest.mark.observability
