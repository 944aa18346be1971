"""paddle.reader — legacy reader decorators.

Reference: python/paddle/reader/decorator.py (cache:52, map_readers:92,
shuffle:134, chain:183, compose:248, buffered:308, firstn:367,
xmap_readers:412, multiprocess_reader:505). A "reader" is a zero-arg
callable returning an iterator of samples; decorators compose them.
Kept API-faithful: these predate `paddle.io.DataLoader` but CTR/legacy
pipelines still build on them (DataLoader remains the recommended path).
"""
import itertools
import queue as queue_mod
import random
import threading
import traceback

__all__ = ["cache", "map_readers", "shuffle", "chain", "compose",
           "buffered", "firstn", "xmap_readers", "multiprocess_reader",
           "ComposeNotAligned"]


class ComposeNotAligned(ValueError):
    pass


def cache(reader):
    """Materialize once, replay from memory on every call (reference
    decorator.py:52)."""
    all_data = tuple(reader())

    def __impl__():
        return iter(all_data)

    return __impl__


def map_readers(func, *readers):
    """Yield func applied across the zip of the readers' outputs
    (reference decorator.py:92)."""

    def reader():
        rs = [r() for r in readers]
        for vals in zip(*rs):
            yield func(*vals)

    return reader


def shuffle(reader, buf_size):
    """Shuffle within a sliding buffer of `buf_size` samples (reference
    decorator.py:134)."""

    def data_reader():
        buf = []
        for e in reader():
            buf.append(e)
            if len(buf) >= buf_size:
                random.shuffle(buf)
                yield from buf
                buf = []
        if buf:
            random.shuffle(buf)
            yield from buf

    return data_reader


def chain(*readers):
    """Concatenate readers back to back (reference decorator.py:183)."""

    def reader():
        return itertools.chain(*[r() for r in readers])

    return reader


def compose(*readers, **kwargs):
    """Zip readers into flattened tuples; samples must align unless
    check_alignment=False (reference decorator.py:248)."""
    check_alignment = kwargs.pop("check_alignment", True)

    def make_tuple(x):
        return x if isinstance(x, tuple) else (x,)

    def reader():
        rs = [r() for r in readers]
        if not check_alignment:
            for outputs in zip(*rs):
                yield sum((make_tuple(o) for o in outputs), ())
        else:
            for outputs in itertools.zip_longest(*rs):
                if any(o is None for o in outputs):
                    raise ComposeNotAligned(
                        "outputs of readers are not aligned")
                yield sum((make_tuple(o) for o in outputs), ())

    return reader


def buffered(reader, size):
    """Producer thread + bounded queue: pre-reads up to `size` samples
    ahead of the consumer (reference decorator.py:308)."""

    class _End:
        def __init__(self, exc=None):
            self.exc = exc

    def data_reader():
        r = reader()
        q = queue_mod.Queue(maxsize=size)

        def read_worker():
            # the sentinel must reach the queue even on error, or the
            # consumer blocks in q.get() forever
            try:
                for d in r:
                    q.put(d)
            except BaseException as e:  # noqa: BLE001 — re-raised below
                q.put(_End(e))
            else:
                q.put(_End())

        t = threading.Thread(target=read_worker, daemon=True)
        t.start()
        e = q.get()
        while not isinstance(e, _End):
            yield e
            e = q.get()
        if e.exc is not None:
            raise e.exc

    return data_reader


def firstn(reader, n):
    """Only the first n samples (reference decorator.py:367)."""

    def firstn_reader():
        return itertools.islice(reader(), n)

    return firstn_reader


_XMAP_END = object()


def xmap_readers(mapper, reader, process_num, buffer_size, order=False):
    """Parallel `mapper` over samples with `process_num` worker THREADS
    (the reference uses threads too, decorator.py:412 — mappers are
    typically numpy/PIL which release the GIL; for pure-python mappers
    use `paddle.io.DataLoader(num_workers=...)`, real processes)."""

    class _Err:
        def __init__(self, exc):
            self.exc = exc

    def xreader():
        in_q = queue_mod.Queue(buffer_size)
        out_q = queue_mod.Queue(buffer_size)

        def feed():
            # errors surface on out_q; every worker still gets its end
            # marker so the consumer's sentinel count converges
            try:
                for i, sample in enumerate(reader()):
                    in_q.put((i, sample))
            except BaseException as e:  # noqa: BLE001
                out_q.put(_Err(e))
            finally:
                for _ in range(process_num):
                    in_q.put(_XMAP_END)

        def work():
            try:
                while True:
                    item = in_q.get()
                    if item is _XMAP_END:
                        return
                    i, sample = item
                    out_q.put((i, mapper(sample)))
            except BaseException as e:  # noqa: BLE001
                out_q.put(_Err(e))
            finally:
                out_q.put(_XMAP_END)

        threading.Thread(target=feed, daemon=True).start()
        for _ in range(process_num):
            threading.Thread(target=work, daemon=True).start()

        def results():
            finished = 0
            while finished < process_num:
                item = out_q.get()
                if isinstance(item, _Err):
                    raise item.exc
                if item is _XMAP_END:
                    finished += 1
                else:
                    yield item

        if not order:
            for _, mapped in results():
                yield mapped
        else:
            pending, next_i = {}, 0
            for i, mapped in results():
                pending[i] = mapped
                while next_i in pending:
                    yield pending.pop(next_i)
                    next_i += 1
            while next_i in pending:  # drain (a worker died mid-gap is
                yield pending.pop(next_i)  # surfaced by _Err above)
                next_i += 1

    return xreader


def multiprocess_reader(readers, use_pipe=True, queue_size=1000):
    """Run each reader in its own PROCESS, merging samples as they
    arrive (reference decorator.py:505). Uses the fork context (readers
    are usually closures over open files — unpicklable); samples cross
    via an mp.Queue either way (`use_pipe` kept for API compat).

    Start iterating BEFORE the process touches a device: the children
    are forks, and a fork of a process that already holds the chip (or
    runs jax's threads) inherits a runtime it cannot use — a chip
    belongs to one process at a time. `io.DataLoader` workers
    (io/multiprocess.py, forkserver) have no such ordering rule."""
    import multiprocessing as mp

    assert len(readers) > 0, "readers must not be empty"

    _END, _FAIL = "__mp_reader_end__", "__mp_reader_fail__"

    def queue_reader():
        ctx = mp.get_context("fork")
        q = ctx.Queue(queue_size)

        def _read(r):
            # tagged sentinels: a None SAMPLE must not end the stream,
            # and a child exception must fail the parent, not truncate
            try:
                for s in r():
                    q.put(("s", s))
            except BaseException:  # noqa: BLE001 — marshalled to parent
                q.put((_FAIL, traceback.format_exc()))
            else:
                q.put((_END, None))

        procs = [ctx.Process(target=_read, args=(r,), daemon=True)
                 for r in readers]
        for p in procs:
            p.start()
        finished = 0
        while finished < len(readers):
            tag, payload = q.get()
            if tag == _END:
                finished += 1
            elif tag == _FAIL:
                for p in procs:
                    p.terminate()
                raise RuntimeError(
                    f"multiprocess_reader child failed:\n{payload}")
            else:
                yield payload
        for p in procs:
            p.join()

    return queue_reader
