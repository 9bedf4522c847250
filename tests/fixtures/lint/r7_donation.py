"""R7 bite fixture: donated buffers reused after a faulted dispatch
(the ``_dispatch_mixed`` retry caveat).  Parsed, never imported."""


class Engine:
    def __init__(self):
        self._tile_step = self._make_tile_step()
        self._outer_step = self._make_outer_step()
        self._plain_step = self._make_plain_step()

    def _make_tile_step(self):
        @partial(jax.jit, donate_argnums=(1,))
        def tile_step(params, pages, tables):
            return pages

        return tile_step

    def _make_outer_step(self):
        # maker chaining: returns another maker's donating step
        return self._make_tile_step()

    def _make_plain_step(self):
        @jax.jit
        def plain_step(params, pages):  # nothing donated
            return pages

        return plain_step

    def _dispatch_tile(self, *args):
        try:
            return self._tile_step(self.params, self.pool.pages, *args)
        except Exception:
            self._degrade()
            return self._tile_step(self.params, self.pool.pages, *args)  # BITE

    def _dispatch_outer(self, args):
        try:
            return self._outer_step(self.params, self.pool.pages, *args)
        except Exception:
            return self._outer_step(self.params, self.pool.pages, *args)  # BITE

    def _dispatch_rebuilt(self, *args):
        # FINE: the donated operand is rebuilt before the retry
        try:
            return self._tile_step(self.params, self.pool.pages, *args)
        except Exception:
            fresh = self.pool.rebuild_pages()
            return self._tile_step(self.params, fresh, *args)

    def _dispatch_plain(self, *args):
        # FINE: nothing donated, retrying with the same operand is legal
        try:
            return self._plain_step(self.params, self.pool.pages)
        except Exception:
            return self._plain_step(self.params, self.pool.pages)
