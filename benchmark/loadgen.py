"""The one general load generator: a traffic file's parameters and a seed
in, requests out, sent over HTTP from one thread (asyncio) and timed from
when each was *due*.

Every seed gets the same multiset of prompt lengths, output lengths and
arrival gaps (the distributions' quantile grids), in another order and
with other token ids, so that the seed changes which request comes when
and never how much work a window holds.
"""
import asyncio
import json
import math
import random
import statistics
import time


def lognormal_grid(spec, n):
    """``n`` lengths on the quantile grid of a log-normal with the given
    ``median`` and ``sigma``, clipped to [``min``, ``max``]."""
    nd = statistics.NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        x = spec['median'] * math.exp(spec['sigma'] * z)
        out.append(int(min(spec['max'], max(spec['min'], round(x)))))
    return out


def exponential_grid(rate, n):
    """``n`` gaps on the quantile grid of an exponential of mean 1/rate;
    they add up to n/rate to within a fraction of a gap."""
    gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
    scale = (n / rate) / sum(gaps)
    return [g * scale for g in gaps]


class Request:
    __slots__ = ('rid', 'due', 'prompt', 'max_new', 'sent', 'first',
                 'last', 'tokens', 'token_times', 'error', 'measured')

    def __init__(self, rid, due, prompt, max_new):
        self.rid, self.due, self.prompt, self.max_new = rid, due, prompt, \
            max_new
        self.sent = self.first = self.last = None
        self.tokens, self.token_times, self.error = [], [], None
        self.measured = False


def population(traffic, vocab, seed):
    """The cell's fixed population of (prompt, output length), shuffled
    and filled with token ids from the seed."""
    n = int(traffic['population'])
    rng = random.Random(seed)
    prompts = lognormal_grid(traffic['prompt_len'], n)
    outputs = lognormal_grid(traffic['output_len'], n)
    rng.shuffle(prompts)
    rng.shuffle(outputs)
    return [([rng.randrange(vocab) for _ in range(p)], o)
            for p, o in zip(prompts, outputs)]


def open_loop_schedule(traffic, vocab, seed, seconds):
    """Requests due over ``ramp_seconds`` + ``seconds`` at the cell's fixed
    rate: seeded order of a fixed set of gaps. ``due`` is relative to the
    window's opening, so the ramp's are negative."""
    rate, ramp = float(traffic['rate_per_s']), float(traffic['ramp_seconds'])
    pop = population(traffic, vocab, seed)
    n = int(round(rate * (ramp + seconds)))
    gaps = exponential_grid(rate, n)
    random.Random(seed + 1).shuffle(gaps)
    out, t = [], -ramp
    for i, g in enumerate(gaps):
        t += g
        prompt, max_new = pop[i % len(pop)]
        out.append(Request(i, t, prompt, max_new))
    return out


async def _post(port, req, stream, clock):
    """One ``POST /generate``; fills the request's times and tokens."""
    body = json.dumps({'tokens': req.prompt, 'max_new_tokens': req.max_new,
                       'stream': stream}).encode()
    reader = writer = None
    try:
        reader, writer = await asyncio.open_connection('127.0.0.1', port)
        req.sent = clock()
        writer.write(b'POST /generate HTTP/1.1\r\nHost: x\r\n'
                     b'Content-Type: application/json\r\n'
                     b'Connection: close\r\nContent-Length: %d\r\n\r\n'
                     % len(body) + body)
        await writer.drain()
        status = await reader.readline()
        length = None
        while True:
            line = await reader.readline()
            if line in (b'\r\n', b'\n', b''):
                break
            if line.lower().startswith(b'content-length:'):
                length = int(line.split(b':')[1])
        if b' 200 ' not in status:
            rest = await reader.read(2000)
            raise RuntimeError('%s %s' % (status.strip().decode(),
                                          rest[:300].decode('replace')))
        if not stream:
            done = json.loads(await reader.readexactly(length))
            now = clock()
            req.tokens = done['tokens']
            req.first = req.last = now
            req.token_times = [now] * len(req.tokens)
        else:
            done = None
            while done is None:
                line = await reader.readline()
                if not line:
                    raise RuntimeError('stream ended without a done line')
                if not line.startswith(b'{'):
                    continue
                now = clock()
                rec = json.loads(line)
                if rec.get('done'):
                    done = rec
                else:
                    req.tokens.append(rec['token'])
                    req.token_times.append(now)
            if req.token_times:
                req.first, req.last = req.token_times[0], \
                    req.token_times[-1]
        if done.get('error') or done.get('degraded') \
                or list(done['tokens']) != list(req.tokens):
            raise RuntimeError('bad done line: %r' % {
                k: done.get(k) for k in ('error', 'degraded',
                                         'finish_reason')})
    except Exception as exc:            # a request that fails is counted
        req.error = '%s: %s' % (type(exc).__name__, exc)
    finally:
        if writer is not None:
            writer.close()


async def _open_loop(port, schedule, t_open, seconds, grace, clock):
    tasks = []
    for req in schedule:
        wait = t_open + req.due - clock()
        if wait > 0:
            await asyncio.sleep(wait)
        req.measured = 0.0 <= req.due < seconds
        tasks.append(asyncio.ensure_future(_post(port, req, True, clock)))
    await _finish(tasks, t_open + seconds + grace, clock)


def streamed(traffic):
    """An open loop streams its replies; a closed loop says which."""
    return traffic['loop'] == 'open' or bool(traffic.get('stream'))


async def _closed_loop(port, pop, clients, stream, t_open, seconds, grace,
                       clock, sent):
    nxt = iter(range(10 ** 9))

    async def client():
        while clock() < t_open + seconds:
            i = next(nxt)
            prompt, max_new = pop[i % len(pop)]
            req = Request(i, None, prompt, max_new)
            sent.append(req)
            await _post(port, req, stream, clock)
            req.measured = req.last is not None and \
                t_open <= req.last < t_open + seconds

    tasks = [asyncio.ensure_future(client()) for _ in range(clients)]
    await _finish(tasks, t_open + seconds + grace, clock)


async def _finish(tasks, deadline, clock):
    """Wait for every request, a grace past the window's close; one that
    has not come by then never came."""
    if tasks:
        _done, late = await asyncio.wait(
            tasks, timeout=max(0.0, deadline - clock()))
        for t in late:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)


def drive(port, traffic, vocab, seed, seconds, on_open=None, on_close=None,
          clock=time.time):
    """Run the cell's traffic against ``port``. Returns (requests, t_open):
    every request sent, ramp included, and the window's opening time.
    ``on_open`` and ``on_close`` are called as the window opens and
    closes."""
    ramp, grace = float(traffic['ramp_seconds']), \
        float(traffic.get('grace_seconds', 60.0))
    sent = []

    async def main():
        t_open = clock() + ramp
        loop = asyncio.get_running_loop()
        if on_open is not None:
            loop.call_later(ramp, on_open)
        if on_close is not None:
            loop.call_later(ramp + seconds, on_close)
        if traffic['loop'] == 'open':
            sent.extend(open_loop_schedule(traffic, vocab, seed, seconds))
            await _open_loop(port, sent, t_open, seconds, grace, clock)
        elif traffic['loop'] == 'closed':
            pop = population(traffic, vocab, seed)
            await _closed_loop(port, pop, int(traffic['clients']),
                               streamed(traffic), t_open, seconds, grace,
                               clock, sent)
        else:
            raise ValueError('loop is %r' % traffic['loop'])
        return t_open

    return sent, asyncio.run(main())


def percentile(values, q):
    """The q-th percentile by the nearest-rank rule; None when empty."""
    if not values:
        return None
    xs = sorted(values)
    return xs[min(len(xs) - 1, max(0, math.ceil(q / 100.0 * len(xs)) - 1))]
