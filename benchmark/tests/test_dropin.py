"""A configuration, a kind of traffic, a traffic mix and per-layer
metrics (one declared as data, one as code) added as files of their own
are found and run by name, with no edit to a file that is there."""

import helpers


def test_cell_added_as_files(tmp_path):
    r = helpers.run(tmp_path, trace=True)
    assert r["correct"], r["compared"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    # per-layer metrics of the traced run, the two new ones among them
    assert r["metrics"]["assign_s"]["unit"] == "s/batch"
    assert r["metrics"]["assign_s"]["value"] > 0
    assert r["metrics"]["proved_batches"]["value"] == r["attempted"]
    # readers of the chip's cells find nothing here and stay silent
    assert "commit_hbm_roofline" not in r["metrics"]
    assert "device_idle.prove" not in r["metrics"]
    assert r["device"]["platform"] == "cpu"


def test_a_new_kind_of_traffic_is_one_file(tmp_path):
    import traffic

    _, bench_dir = helpers.drop_in(tmp_path)
    mix, kind = traffic.load_mix(
        bench_dir + "/traffic/transfer3x2.json", bench_dir)
    assert mix["kind"] == "pooled_transfers"
    t = kind.Traffic(mix, 2**31 + 77)
    recipients = {x.to for k in range(5) for blk in t.batch(k) for x in blk}
    assert len(recipients) <= mix["recipient_pool"]
    # its reference follows its own transfers
    states = kind.expected_states(t, 4)
    assert set(states[4]) - {t.sender, b"\x00" * 20} <= recipients
    # the kind that was there still reads its own mixes
    mix0, kind0 = traffic.load_mix(
        bench_dir + "/traffic/transfer10-backlog.json", bench_dir)
    assert kind0.Traffic(mix0, 5).batch(0)


def test_the_same_seed_sends_the_same_bytes(tmp_path):
    from traffic import load_mix

    _, bench_dir = helpers.drop_in(tmp_path)
    for name in ("transfer3x2", "transfer10-backlog"):
        mix, kind = load_mix(f"{bench_dir}/traffic/{name}.json", bench_dir)
        seed = 2**31 + 1234567
        a, b = kind.Traffic(mix, seed), kind.Traffic(mix, seed)
        b.batch(3)                      # asked for in another order
        for k in range(4):
            assert [b.signed(t) for blk in b.batch(k) for t in blk] == \
                [a.signed(t) for blk in a.batch(k) for t in blk]
        assert a.genesis() == b.genesis()
        other = kind.Traffic(mix, seed + 1)
        assert other.sender != a.sender
        # another seed: other keys and values, the same amount of work
        assert [len(blk) for blk in other.batch(0)] == \
            [len(blk) for blk in a.batch(0)]
