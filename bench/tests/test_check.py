"""The check compares requests still in flight as well as finished ones,
always the deepest context among them, and counts a collected output
that is not what was streamed."""
import numpy as np

from bench import check, loadgen, replay


def sent(prompt_len, served, max_new, finished=False, output=None):
    r = loadgen.Request(np.zeros(prompt_len, np.int32), max_new, None, 0.0)
    s = replay.Sent(r, 0.0, True, t_first=0.0, served=list(served))
    if finished:
        s.output = np.asarray(served if output is None else output, np.int32)
    return s


def record(sents):
    rec = replay.Record()
    rec.sent = dict(enumerate(sents))
    return rec


def test_sample_holds_the_deepest_and_the_longest_in_flight():
    sents = [sent(40, range(5), 5, finished=True) for _ in range(8)]
    sents.append(sent(500, range(100), 900))     # deepest, in flight
    sents.append(sent(20, range(300), 900))      # most tokens, in flight
    sents.append(sent(30, [], 900))              # nothing streamed yet
    for seed in range(5):
        chosen = {id(s) for s in check.sample(sents, seed)}
        assert len(chosen) == check.SAMPLE
        assert id(sents[8]) in chosen and id(sents[9]) in chosen
        assert id(sents[10]) not in chosen


def test_counts_read_a_stream_the_output_disagrees_with():
    good = sent(10, [1, 2, 3], 3, finished=True)
    bad = sent(10, [1, 2, 3], 3, finished=True, output=[1, 2, 4])
    short = sent(10, [1, 2], 3, finished=True)
    c = check.counts(record([good, bad, short]))
    assert c["stream_mismatch"]["value"] == 1
    assert c["short_outputs"]["value"] == 1
    assert c["none_served"]["value"] == 0
    assert check.counts(record([sent(10, [], 3)]))["none_served"]["value"] \
        == 1
