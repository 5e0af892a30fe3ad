"""The faults planted in the timed path of a Byzantine cell
(``chipbench/engines/byzantine.py``); each must come out not correct.

Each fault is a function ``(monkeypatch, workload) -> hook | None``: it
breaks the program through ``monkeypatch``, or returns a ``hook(cell)``
that wraps the built cell before its warm call (``run.run_cell``'s
``cell_hook``).
"""
import jax.numpy as jnp


def _exchange_returns_state(monkeypatch, workload):
    """The exchange step returns its state unchanged: push-sum delivers
    nothing (social), the trimmed gossip hears no neighbour (Byzantine)."""
    from repro.core import sweeps

    monkeypatch.setattr(
        sweeps, "_sparse_gossip",
        lambda key, t, r, rt, F, **k: (jnp.zeros_like(r),
                                       jnp.zeros(r.shape[:1], r.dtype)))
    return None


def _half_the_batch(monkeypatch, workload):
    """Half of the batch left out: the second half of the edges carries
    nothing (social), the second half of the grid's seeds repeats the
    first half (Byzantine)."""
    def hook(cell):
        from repro.core.sweeps import run_byzantine_grid

        def call(i):
            half = cell.seeds(i)[: cell.K // 2]
            return run_byzantine_grid(cell._model, [cell._cfg], cell.T,
                                      seeds=(half * 2)[: cell.K],
                                      plan=cell._plan)
        cell.call = call
        return cell
    return hook


def _ps_fusion_broken(monkeypatch, workload):
    """The parameter server's fusion broken: left out (social: the
    representatives keep their own values), or its trim dropped
    (Byzantine: the pool's F largest and F smallest answers, a Byzantine
    representative's among them, are averaged in)."""
    from repro.core import hps

    pool = hps.ps_trimmed_pool
    monkeypatch.setattr(hps, "ps_trimmed_pool",
                        lambda vals, valid, F, **k: pool(vals, valid, 0, **k))
    return None


def _answer_altered(monkeypatch, workload):
    """One answer altered where it is produced."""
    def hook(cell):
        keep = cell.keep

        def altered(out):
            kept = list(keep(out))
            kept[0] = kept[0].at[0, 5].multiply(-1.0)
            return tuple(kept)
        cell.keep = altered
        return cell
    return hook


FAULTS = [_exchange_returns_state, _half_the_batch, _ps_fusion_broken,
          _answer_altered]
