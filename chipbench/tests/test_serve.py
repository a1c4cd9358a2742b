from types import SimpleNamespace

from chipbench import serve


def stream(index, prompt_len, times, error=None):
    return SimpleNamespace(req=SimpleNamespace(index=index,
                                               prompt_len=prompt_len),
                           times=list(times), tokens=[7] * len(times),
                           error=error)


def test_pick_streams_takes_the_longest_context_then_spreads():
    sent = [stream(i, 100 + i, [10.0 + i, 11.0 + i, 12.0 + i])
            for i in range(20)]
    sent[6] = stream(6, 900, [16.0, 17.0])
    picked = serve.pick_streams(sent, 10.0, 40.0, 4)
    assert [s.req.index for s in picked] == [6, 3, 10, 16]
    assert serve.pick_streams(sent, 10.0, 40.0, 0) == []


def test_pick_streams_leaves_out_what_the_window_did_not_see():
    sent = [stream(0, 50, [1.0, 2.0]),             # over before the window
            stream(1, 50, [9.0, 10.5]),            # one token inside
            stream(2, 999, [11.0]),                # a single token
            stream(3, 999, [11.0, 12.0], "boom"),  # failed
            stream(4, 60, [50.0, 51.0])]           # after the window
    assert [s.req.index for s in serve.pick_streams(sent, 10.0, 40.0, 4)] == [1]
    assert serve.pick_streams(sent[:1], 10.0, 40.0, 4) == []
