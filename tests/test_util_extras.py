"""ActorPool / Queue / multiprocessing.Pool tests (reference:
python/ray/tests/test_actor_pool.py, test_queue.py,
python/ray/util/multiprocessing tests)."""

import pytest

import ray_tpu
from ray_tpu.util.actor_pool import ActorPool
from ray_tpu.util.multiprocessing import Pool
from ray_tpu.util.queue import Empty, Full, Queue


@pytest.fixture(scope="module", autouse=True)
def _cluster(ray_cluster):
    yield


def _doubler_cls():
    # defined inside a function so cloudpickle serializes it by VALUE —
    # workers cannot import the test module
    class Doubler:
        def double(self, v):
            return 2 * v

        def slow_double(self, v):
            import time

            time.sleep(0.1 * (v % 3))
            return 2 * v

    return Doubler


def test_actor_pool_map_ordered():
    D = ray_tpu.remote(_doubler_cls())
    pool = ActorPool([D.remote(), D.remote()])
    out = list(pool.map(lambda a, v: a.double.remote(v), [1, 2, 3, 4]))
    assert out == [2, 4, 6, 8]


def test_actor_pool_map_unordered():
    D = ray_tpu.remote(_doubler_cls())
    pool = ActorPool([D.remote(), D.remote()])
    out = list(pool.map_unordered(
        lambda a, v: a.slow_double.remote(v), list(range(6))))
    assert sorted(out) == [0, 2, 4, 6, 8, 10]


def test_actor_pool_submit_get_next():
    D = ray_tpu.remote(_doubler_cls())
    pool = ActorPool([D.remote()])
    pool.submit(lambda a, v: a.double.remote(v), 10)
    pool.submit(lambda a, v: a.double.remote(v), 20)  # queued behind
    assert pool.has_next()
    assert pool.get_next() == 20
    assert pool.get_next() == 40
    assert not pool.has_next()


def test_actor_pool_push_pop():
    D = ray_tpu.remote(_doubler_cls())
    a1, a2 = D.remote(), D.remote()
    pool = ActorPool([a1])
    idle = pool.pop_idle()
    assert idle is a1
    pool.push(a1)
    pool.push(a2)
    with pytest.raises(ValueError):
        pool.push(a2)
    out = list(pool.map(lambda a, v: a.double.remote(v), [1, 2]))
    assert out == [2, 4]


def test_queue_basics():
    q = Queue(maxsize=2)
    assert q.empty() and not q.full() and len(q) == 0
    q.put(1)
    q.put_nowait(2)
    assert q.full() and q.qsize() == 2
    with pytest.raises(Full):
        q.put_nowait(3)
    assert q.get() == 1
    assert q.get_nowait() == 2
    with pytest.raises(Empty):
        q.get_nowait()
    q.shutdown()


def test_queue_blocking_timeout_and_batches():
    q = Queue()
    with pytest.raises(Empty):
        q.get(timeout=0.2)
    q.put_nowait_batch([1, 2, 3])
    assert q.get_nowait_batch(2) == [1, 2]
    with pytest.raises(Empty):
        q.get_nowait_batch(5)
    q.shutdown()


def test_queue_producer_consumer_across_tasks():
    q = Queue()

    @ray_tpu.remote
    def producer(q, n):
        for i in range(n):
            q.put(i)
        return True

    @ray_tpu.remote
    def consumer(q, n):
        return sum(q.get(timeout=30) for _ in range(n))

    # Queue pickles by actor handle, so tasks on any worker share it
    p = producer.remote(q, 10)
    c = consumer.remote(q, 10)
    assert ray_tpu.get(c, timeout=120) == 45
    assert ray_tpu.get(p, timeout=30)
    q.shutdown()


def test_mp_pool_map_and_apply():
    def sq(x):
        return x * x

    with Pool(processes=2) as pool:
        assert pool.map(sq, range(10)) == [x * x for x in range(10)]
        assert pool.apply(divmod, (7, 3)) == (2, 1)
        r = pool.apply_async(sq, (6,))
        assert r.get(timeout=60) == 36


def test_mp_pool_starmap_and_imap():
    def sq(x):
        return x * x

    with Pool(processes=2) as pool:
        assert pool.starmap(pow, [(2, 3), (3, 2)]) == [8, 9]
        assert list(pool.imap(sq, range(6), chunksize=2)) == \
            [0, 1, 4, 9, 16, 25]
        assert sorted(pool.imap_unordered(sq, range(6), chunksize=2)) == \
            [0, 1, 4, 9, 16, 25]


def test_mp_pool_closed_raises():
    pool = Pool(processes=1)
    pool.close()
    with pytest.raises(ValueError):
        pool.map(abs, [1])
    pool.terminate()
