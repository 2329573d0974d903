"""Wire protocol + RPC service (the Thrift analogue) tests."""
import threading

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import backends as BK
from repro.core import service as SV
from repro.core import wire
from repro.data import qa as QA
from repro.data.tokenizer import HashingTokenizer
from repro.models import sm_cnn


def test_wire_roundtrip_single():
    frame = wire.encode_get_score("what is foo", "foo is bar")
    t, payload = frame[4], frame[5:]
    pairs = wire.decode_request(t, payload)
    assert pairs == [("what is foo", "foo is bar")]


def test_wire_roundtrip_batch():
    pairs = [(f"q{i}", f"a{i} text") for i in range(5)]
    frame = wire.encode_get_score_batch(pairs)
    t, payload = frame[4], frame[5:]
    assert wire.decode_request(t, payload) == pairs


def test_wire_reply_roundtrip():
    for scores in ([0.5], [0.1, 0.9, 0.3333]):
        frame = wire.encode_reply(scores)
        t, payload = frame[4], frame[5:]
        out = wire.decode_reply(t, payload)
        np.testing.assert_allclose(out, scores)


def test_wire_error_raises():
    frame = wire.encode_error("boom")
    t, payload = frame[4], frame[5:]
    with pytest.raises(RuntimeError, match="boom"):
        wire.decode_reply(t, payload)


def test_wire_unicode():
    frame = wire.encode_get_score("café ≠ caffé", "naïve answer")
    pairs = wire.decode_request(frame[4], frame[5:])
    assert pairs[0][0] == "café ≠ caffé"


@pytest.fixture(scope="module")
def service():
    cfg = reduced(get_config("sm-cnn"))
    params = sm_cnn.init_sm_cnn(jax.random.PRNGKey(0), cfg)
    corpus = QA.generate_corpus(n_docs=20, n_questions=5, seed=3)
    tok = HashingTokenizer(cfg.vocab_size)
    scorer = BK.make_scorer("jit", params, cfg, buckets=(16, 64))
    handler = SV.QuestionAnsweringHandler(scorer, tok, corpus.idf, cfg.max_len)
    srv = SV.SimpleServer(handler).start_background()
    yield srv, handler, corpus
    srv.stop()


def test_service_single_and_batch_agree_with_direct(service):
    srv, handler, corpus = service
    cl = SV.Client(srv.address)
    q = corpus.questions[0]
    a = corpus.documents[0][0]
    s_rpc = cl.get_score(q, a)
    s_direct = float(handler.get_scores([(q, a)])[0])
    assert abs(s_rpc - s_direct) < 1e-9
    batch = cl.get_score_batch([(q, corpus.documents[0][i]) for i in range(3)])
    direct = handler.get_scores([(q, corpus.documents[0][i]) for i in range(3)])
    np.testing.assert_allclose(batch, direct, rtol=1e-9)
    cl.close()


def test_service_survives_bad_pair_and_recovers(service):
    srv, handler, corpus = service
    cl = SV.Client(srv.address)
    s = cl.get_score("", "")       # empty strings must not kill the server
    assert 0.0 <= s <= 1.0
    s2 = cl.get_score(corpus.questions[0], corpus.documents[0][0])
    assert 0.0 <= s2 <= 1.0
    cl.close()


def test_service_sequential_clients(service):
    """TSimpleServer semantics: one connection at a time, served fully."""
    srv, handler, corpus = service
    results = []

    def worker():
        cl = SV.Client(srv.address)
        results.append(cl.get_score(corpus.questions[0],
                                    corpus.documents[0][0]))
        cl.close()

    threads = [threading.Thread(target=worker) for _ in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert len(results) == 3
    assert len(set(round(r, 9) for r in results)) == 1


def test_client_shared_by_threads_keeps_replies_apart(service):
    """Threads sharing one Client (as the fabric's probe thread and its
    supervisor share a control connection) each get their own reply:
    batch sizes and scores never cross, under a short switch interval."""
    import os
    import sys
    srv, handler, corpus = service
    q = corpus.questions[0]
    answers = [corpus.documents[i % len(corpus.documents)][0]
               for i in range(8)]
    want = {n: handler.get_scores([(q, a) for a in answers[:n]])
            for n in range(1, 9)}
    cl = SV.Client(srv.address)
    errors = []

    def worker(n):
        try:
            for _ in range(15):
                got = cl.get_score_batch([(q, a) for a in answers[:n]])
                np.testing.assert_allclose(got, want[n], rtol=1e-9)
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(1 + i % 8,))
                   for i in range((os.cpu_count() or 1) + 4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
        cl.close()
    assert not any(t.is_alive() for t in threads)
    assert errors == []
