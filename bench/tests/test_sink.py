"""Time to first token and gaps between tokens, on scripted stamps."""
import pytest

from bench.sink import Sink, WindowClosed


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_ttft_gaps_tokens_with_a_prefill_stall_and_a_waiting_request():
    clock = Clock()
    sk = Sink(slots=2, start=0.0, deadline=10.0, clock=clock)
    # one job of three requests, two slots, three tokens each
    sk.begin_job([5, 7, 4], 3, submitted=0.0)
    clock.t = 1.0
    sk.record_prefill(5, 1.0)           # request 0 -> slot 0
    clock.t = 4.0                       # request 1's prefill stalls 3 s
    sk.record_prefill(7, 3.0)           # request 1 -> slot 1
    clock.t = 4.5
    sk.record_decode([6, 8], 0.5)       # both slots, contexts plen + 1
    clock.t = 5.0
    sk.record_decode([7, 9], 0.5)       # both retire (3 tokens each)
    clock.t = 11.0                      # past the deadline
    with pytest.raises(WindowClosed):
        sk.record_prefill(4, 6.0)       # request 2 never got its token
    st = sk.close()
    assert st.ttft_s == [1.0, 4.0, 10.0]    # the waiting one: 10 - 0
    # slot 0: 4.5 - 1.0 (from its prefill), then 0.5; slot 1: 0.5, 0.5
    assert st.gaps_s == [3.5, 0.5, 0.5, 0.5]
    assert st.tokens == 2 + 2 + 2
    assert st.prefill_s == 4.0 and st.decode_s == 1.0
    assert st.decode_ctx == [[6, 8], [7, 9]]
    assert st.requests == 3 and st.seconds == 10.0


def test_late_decode_counts_for_the_trace_but_not_the_window():
    clock = Clock()
    sk = Sink(slots=1, start=0.0, deadline=2.0, clock=clock)
    sk.begin_job([3], 4, submitted=0.0)
    clock.t = 1.0
    sk.record_prefill(3, 1.0)
    clock.t = 2.5
    with pytest.raises(WindowClosed):
        sk.record_decode([4], 1.5)
    st = sk.close()
    assert st.tokens == 1 and st.decode_ctx == []
    assert st.traced_decode_ctx == [[4]]


def test_a_schedule_other_than_the_engines_raises():
    sk = Sink(slots=2, start=0.0, deadline=9.0, clock=Clock())
    sk.begin_job([5, 7], 2, submitted=0.0)
    sk.record_prefill(5)
    with pytest.raises(RuntimeError):
        sk.record_decode([6, 8])        # only slot 0 is live
