"""The metric arithmetic: rates over the whole window, exact-rank tails,
an unserved request counted as missing at the window's end."""
import math

import pytest

from bench import harness, stats


def reader(name):
    return harness.metric_reader(harness_root(), name)


def harness_root():
    import os
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def make_run(requests, due, steps=(), admits=()):
    run = harness.Run(root=harness_root(), cell={"name": "x"}, seed=0,
                      seconds=10.0, trace=False, t_start=0.0)
    run.window = (100.0, 110.0)
    run.data.update(requests=requests, due=due, steps=list(steps),
                    admits=list(admits))
    return run


def req(uid, due, admit, times, finish=None):
    return {"uid": uid, "due": due, "admit": admit, "times": times,
            "tokens": [1] * len(times), "prompt": (1,), "finish": finish}


def test_exact_rank_percentile():
    xs = list(range(1, 21))            # 1..20
    assert stats.percentile(xs, 95) == 19
    assert stats.percentile(xs, 50) == 10
    assert stats.percentile([5.0], 95) == 5.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_tokens_per_s_counts_tokens_inside_the_window_only():
    run = make_run([req(0, 99.0, 99.0, [99.5, 100.5, 101.0, 111.0]),
                    req(1, 105.0, 105.0, [105.2, 105.4])], due=[(1, 105.0)])
    assert reader("tokens_per_s").read(run) == pytest.approx(4 / 10.0)


def test_ttft_counts_a_request_with_no_first_token_at_the_window_end():
    served = [req(i, 100.0 + 0.4 * i, 100.0 + 0.4 * i,
                  [100.0 + 0.4 * i + 0.1]) for i in range(18)]
    # two never served: due 2 s and 1 s before the close
    due = [(r["uid"], r["due"]) for r in served] + [(98, 108.0), (99, 109.0)]
    run = make_run(served, due)
    # 20 samples: rank ceil(0.95 * 20) = 19 is the nearer unserved one
    assert reader("ttft_p95_ms").read(run) == pytest.approx(1000.0)


def test_queue_wait_of_an_unadmitted_request_runs_to_the_close():
    run = make_run([req(0, 101.0, 101.5, [101.6])],
                   due=[(0, 101.0), (1, 108.0)])
    assert reader("sched.queue_p95_ms").read(run) == pytest.approx(2000.0)


def test_step_time_is_total_over_steps_and_itl_is_a_gap_tail():
    steps = [(100.0 + i, 100.0 + i + 0.02 * (i + 1), 4, 40) for i in range(5)]
    run = make_run([req(0, 100.0, 100.0, [100.0, 100.1, 100.3, 100.6])],
                   due=[(0, 100.0)], steps=steps)
    assert reader("engine.step_ms").read(run) == pytest.approx(60.0)
    assert reader("itl_p95_ms").read(run) == pytest.approx(300.0)


def test_inter_token_gaps_ending_in_the_window_over_every_request():
    # gaps ending before the window (99.5 -> 99.9) are left out
    run = make_run([req(0, 99.0, 99.0, [99.5, 99.9, 100.1, 100.4]),
                    req(1, 101.0, 101.0, [101.0, 101.1, 111.0])],
                   due=[(1, 101.0)])
    # gaps in the window: 0.2, 0.3, 0.1
    assert reader("itl_p95_ms").read(run) == pytest.approx(300.0)


def test_readers_return_nothing_where_there_is_nothing_to_read():
    run = harness.Run(root=harness_root(), cell={"name": "x"}, seed=0,
                      seconds=10.0, trace=True, t_start=0.0)
    for name in ("tokens_per_s", "ttft_p95_ms", "itl_p95_ms", "rows_per_min",
                 "pred_accuracy", "device.idle_share.serve",
                 "device.idle_share.char", "model.step_mfu"):
        assert reader(name).read(run) is None


def test_pred_accuracy_is_a_geometric_mean_of_ratios():
    run = make_run([], [])
    run.data = {"heldout_predicted_s": {"a": 1.0, "b": 4.0},
                "heldout_measured_s": {"a": 2.0, "b": 1.0}}
    assert reader("pred_accuracy").read(run) == pytest.approx(
        math.sqrt(0.5 * 0.25))


def test_heldout_time_is_the_median_block_so_one_stall_does_not_move_it(
        monkeypatch):
    import time

    import jax.numpy as jnp

    from bench.drivers import characterize

    monkeypatch.setattr(characterize, "BLOCK_S", 0.01)
    calls = [0]
    out = jnp.zeros(())

    def program():
        calls[0] += 1
        time.sleep(0.5 if calls[0] == 8 else 0.001)
        return out

    t = characterize.time_heldout({"p": (program, ())})["p"]
    assert 0.001 <= t < 0.005
