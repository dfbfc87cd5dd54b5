"""The profiler as a switch. A traced run's window is the traced stretch
itself (``trace_seconds`` of the traffic file, at most ``--seconds``): every
per-layer number is then of the same stretch, and nothing is measured while
the profiler writes its file. The trace goes to a fixed directory inside
the checkout and is reduced and removed afterwards."""
import glob
import os
import shutil
import threading


class Tracer:
    """``start()`` as the window opens, ``stop()`` as it closes (idempotent);
    both do nothing in an untraced run."""

    def __init__(self, ctx):
        self.on = ctx.trace
        self.dir = ctx.trace_dir
        self.stopped = False
        self._lock = threading.Lock()   # serving starts and stops it off-thread

    def start(self):
        if not self.on:
            return
        import jax
        with self._lock:
            self._start(jax)

    def _start(self, jax):
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        # no Python tracer: it slows the host it is meant to watch
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)

    def stop(self):
        if not self.on:
            return
        import jax
        with self._lock:        # a second caller waits for the file
            if not self.stopped:
                jax.profiler.stop_trace()
                self.stopped = True

    def reduce(self, n_devices, **kw):
        """The traced stretch's reduction (``benchmark/xplane.py``), or
        None in an untraced run."""
        if not self.on:
            return None
        from . import xplane
        self.stop()
        files = glob.glob(os.path.join(self.dir, 'plugins', 'profile', '*',
                                       '*.xplane.pb'))
        if not files:
            raise RuntimeError('the profiler wrote no xplane under %s'
                               % self.dir)
        out = xplane.reduce(max(files, key=os.path.getmtime), n_devices,
                            **kw)
        shutil.rmtree(self.dir, ignore_errors=True)
        return out


def annotate(name):
    """A host span on the profiler's clock around a call into a layer."""
    import jax
    return jax.profiler.TraceAnnotation(name)
