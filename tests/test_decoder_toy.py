"""tests/decoder_toy.py's recorded run: what stands in the Executor's place
when `check.run_check` compares a toy Program a second time gives the
recorded fetches to the recorded question and refuses every other. (That
a second comparison compiles nothing and a moved rule still fails it:
tests/test_olmoe.py, on the cheapest toy cell.)"""
import numpy as np
import pytest

from decoder_toy import RecordedRun


class _Var:
    def __init__(self, name):
        self.name = name


def test_the_recorded_run_answers_its_own_feed_and_fetch_list_only():
    feed = {'input_ids': np.arange(6).reshape(2, 3),
            'labels': np.arange(6).reshape(2, 3) + 1}
    fetched = [np.float32(2.5), np.ones((3, 2), 'float32')]
    run = RecordedRun(feed, [_Var('loss'), 'w@GRAD'], fetched)
    # Variables or names, a copy of the feed: the same question
    again = run.run(None, feed={k: v.copy() for k, v in feed.items()},
                    fetch_list=['loss', _Var('w@GRAD')])
    assert again[0] == fetched[0] and again[1] is fetched[1]
    other = dict(feed, labels=feed['labels'] + 1)
    for asked in (dict(feed=other, fetch_list=['loss', 'w@GRAD']),
                  dict(feed={'input_ids': feed['input_ids']},
                       fetch_list=['loss', 'w@GRAD']),
                  dict(feed=dict(feed, more=np.zeros(1)),
                       fetch_list=['loss', 'w@GRAD']),
                  dict(feed=feed, fetch_list=['loss']),
                  dict(feed=feed, fetch_list=['w@GRAD', 'loss']),
                  dict(feed=feed, fetch_list=['loss', 'v@GRAD'])):
        with pytest.raises(AssertionError, match='not the recorded'):
            run.run(None, **asked)
