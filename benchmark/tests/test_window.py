"""The completion-to-completion arithmetic on synthetic timelines."""
import pytest

import window


def op(stream, start, end, nbytes=100, ok=True, kind="put", due=None):
    return {"stream": stream, "kind": kind, "bytes": nbytes, "ok": ok,
            "t_due": start if due is None else due, "t_start": start, "t_end": end}


def back_to_back(stream, t_from, t_to, each):
    out, t = [], t_from
    while t < t_to:
        out.append(op(stream, t, t + each))
        t += each
    return out


def test_edge_objects_are_excluded_from_bytes_and_time():
    # one stream, 1 s objects from -0.5 s: the objects straddling 0 and 10 are left out
    ops = back_to_back(0, -0.5, 11, 1.0)
    assert len(window.in_window(ops, 0, 10)) == 9
    assert window.c2c_bytes_per_s(ops, 0, 10, "put") == pytest.approx(100.0)
    # the plain accounting credits the straddler that finished inside
    assert window.fixed_window_bytes_per_s(ops, 0, 10, "put") == pytest.approx(100.0)


def test_window_shift_does_not_move_c2c_but_moves_fixed_window():
    ops = back_to_back(0, -0.9, 12, 3.0)  # 3 s objects, 10 s window: the edge is 30% of it
    c2c = [window.c2c_bytes_per_s(ops, lo, lo + 10, "put") for lo in (0.0, 0.5, 1.0, 1.5)]
    assert c2c == pytest.approx([100 / 3] * 4)
    fixed = {round(window.fixed_window_bytes_per_s(ops, lo, lo + 10, "put"), 6) for lo in (0.0, 0.5, 1.0, 1.5, 2.5)}
    assert len(fixed) > 1


def test_stall_inside_a_stream_is_counted():
    ops = [op(0, 0, 1), op(0, 1, 2), op(0, 5, 6), op(0, 6, 7)]  # 3 s of nothing between 2 and 5
    assert window.c2c_bytes_per_s(ops, 0, 10, "put") == pytest.approx(400 / 7)


def test_streams_add_and_failed_ops_keep_their_time():
    ops = back_to_back(0, 0, 10, 1.0) + back_to_back(1, 0.5, 9.5, 1.0)
    assert window.c2c_bytes_per_s(ops, 0, 10, "put") == pytest.approx(200.0)
    ops[3]["ok"] = False
    assert window.c2c_bytes_per_s(ops, 0, 10, "put") == pytest.approx(190.0)
    assert window.c2c_bytes_per_s(ops, 0, 10, "get") is None


def test_due_latency_counts_the_wait_behind_a_stall():
    # due every 0.1 s, the server stalls 1 s at t=1: ops due during the stall wait for it
    ops = [op(0, max(d, 2.0) if 1.0 <= d < 2.0 else d, (max(d, 2.0) if 1.0 <= d < 2.0 else d) + 0.01, due=d)
           for d in [i / 10 for i in range(40)]]
    lat = window.due_latencies_ms(ops, 0, 4)
    assert max(lat) == pytest.approx(1010.0)
    assert window.percentile(lat, 50) == pytest.approx(10.0)
    assert window.percentile(lat, 95) > 800


def test_percentile_and_timeline():
    assert window.percentile([], 50) is None
    assert window.percentile([1, 2, 3, 4], 50) == pytest.approx(2.5)
    assert window.percentile(list(range(101)), 95) == pytest.approx(95)
    ops = [op(0, 0.2, 0.7), op(0, 0.7, 1.2), op(0, 1.2, 2.9, ok=False), op(0, 2.9, 3.0)]
    assert window.timeline(ops, 0, 3) == [100, 100, 0]
