"""The reference of ``GreedyGenerator``'s decode loop: the host loop as it
was while a decode step was a program of its own (one dispatch, one report
read back, the next step launched from what the host mirrored), over the
SAME step body (``GreedyGenerator.step``), and the comparison of a
generation by the device's loop with one by this.  Shared by the tests of
every model kind the generator serves (``tests/test_generate.py``,
``test_lfm2_moe.py``, ``test_dots3_note.py``, ``test_joyai_llm_flash.py``).
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict

import jax
import numpy as np

from semantic_router_tpu.models.generate import GreedyGenerator


class Steps:
    """An observer that keeps what every forward was told and told back."""

    def __init__(self) -> None:
        self.opened, self.closed = [], []

    def forward(self, flavour, **facts):
        self.opened.append(dict(facts, flavour=flavour))
        return self

    def stage(self, name):
        return contextlib.nullcontext()

    def done(self, **after):
        self.closed.append(after)

    def decodes(self):
        return [c for o, c in zip(self.opened, self.closed)
                if o["flavour"] == "gen.decode"]


class PerStepGenerator(GreedyGenerator):
    """``GreedyGenerator`` with the host turning once a step: a jitted
    program a step, ``gen.decode`` opened and closed around each, the
    trajectory written as each report arrives.  ``cache`` is what the last
    step left."""

    def _decode(self, obs, key, device, drafted, lengths, ends, commit,
                trajectory, finished) -> None:
        k = self.top_logits

        def entry(row) -> Dict[str, Any]:
            return {"token": int(row[0]), "lse": row[1],
                    "top_ids": row[2:2 + k].astype(np.int32),
                    "top_logits": row[2 + k:]}

        programs = self.__dict__.setdefault("_step_programs", {})
        if key not in programs:
            programs[key] = jax.jit(self.step(key[2]), donate_argnums=(1,))
        step = programs[key]
        if self.drafts:
            self.cache = self._verify_steps(
                obs, step, device, drafted, lengths, commit, trajectory,
                finished, entry)
            return
        cache, tokens, positions, task = device
        n, t = len(trajectory), 0
        while not finished.all():
            live = int((~finished).sum())
            fwd = obs.forward("gen.decode", tokens_real=live, block=t)
            cache, tokens, positions, _, _, out = step(
                self.params, cache, tokens, positions, task)
            report, aux = jax.device_get(out)
            experts, selected = aux.get("experts"), aux.get("selected")
            for i in range(n):
                if finished[i]:
                    continue
                e = dict(entry(report[i]), kind="decode",
                         position=int(lengths[i]) + t)
                if experts is not None:
                    e["experts"] = experts[:, i, None]
                if selected is not None:
                    e["selected"] = selected[:, i]
                trajectory[i].append(e)
                commit(i, e["token"])
            fwd.done(load=aux.get("load"), committed_tokens=live,
                     keys=aux.get("keys"))
            t += 1
        self.cache = cache

    def _verify_steps(self, obs, verify, device, drafted, lengths, commit,
                      trajectory, finished, entry):
        cache, state, positions, task = device
        at = lengths.astype(np.int64)  # the committed token not yet run
        t = 0
        while not finished.all():
            live = np.flatnonzero(~finished)
            fwd = obs.forward("gen.decode", tokens_real=2 * len(live),
                              block=t)
            cache, state, positions, _, _, out = verify(
                self.params, cache, state, positions, task)
            (report, accepted, after), aux = jax.device_get(out)
            committed = 0
            for i in live:
                for slot in range(1 + int(accepted[i])):
                    e = dict(entry(report[i, slot]), kind="decode",
                             position=int(at[i]) + slot,
                             experts=aux["experts"][:, i, slot, None])
                    if slot == 0:
                        e.update(
                            drafted=int(drafted[i, 0]),
                            accepted=bool(accepted[i]),
                            draft=dict(entry(after[i]), position=int(
                                at[i]) + int(accepted[i])))
                    trajectory[i].append(e)
                    commit(i, e["token"])
                    committed += 1
                    if finished[i]:
                        break
                at[i] += 1 + int(accepted[i])
            drafted = after
            fwd.done(load=aux["load"], committed_tokens=committed,
                     drafted=len(live), accepted=int(accepted[live].sum()))
            t += 1
        return cache


def per_step_twin(gen: GreedyGenerator) -> PerStepGenerator:
    """The reference beside ``gen``: its model, parameters, settings and
    compiled prefill, made once a generator and kept on it."""
    if "_per_step_twin" not in gen.__dict__:
        twin = PerStepGenerator.__new__(PerStepGenerator)
        twin.__dict__.update(gen.__dict__)
        gen._per_step_twin = twin
    gen._per_step_twin.eos_token_ids = gen.eos_token_ids
    return gen._per_step_twin


@contextlib.contextmanager
def keeping_the_cache(gen: GreedyGenerator):
    """``gen.cache`` = what its loop gave back, while this is open."""
    real = gen._loop_fn

    def spied(key):
        def fn(*args):
            out = real(key)(*args)
            gen.cache = out[0]
            return out
        return fn
    gen._loop_fn = spied
    try:
        yield gen
    finally:
        del gen._loop_fn  # the class's again


def _same(got, want, what: str, atol: float) -> None:
    if isinstance(want, dict):
        assert sorted(got) == sorted(want), what
        for name, value in want.items():
            _same(got[name], value, f"{what}.{name}", atol)
    elif np.asarray(want).dtype.kind == "f":
        np.testing.assert_allclose(got, want, atol=atol, err_msg=what)
    else:
        assert np.array_equal(got, want), what
        assert np.asarray(got).dtype == np.asarray(want).dtype, what


def assert_the_loop_gives_what_the_hosts_loop_gave(
        loop: GreedyGenerator, texts, max_new_tokens: int,
        atol: float = 1e-5, **kw) -> Dict[str, Any]:
    """One generation by ``loop`` and one by the reference beside it
    (``per_step_twin``): the same tokens, every trajectory entry,
    the caches' final contents, and what the observer learns — ONE
    ``gen.decode`` program whose ``forwards`` is the steps the host's loop
    took and whose facts are the sums of theirs.  Returns what was seen
    (``out``, ``steps``: the per-step observer's decode closes, ``done``:
    the loop's one, or None where no step ran)."""
    seen, want_seen = Steps(), Steps()
    per_step = per_step_twin(loop)
    want = per_step.generate(texts, max_new_tokens, observer=want_seen, **kw)
    with keeping_the_cache(loop):
        got = loop.generate(texts, max_new_tokens, observer=seen, **kw)
    assert [r.token_ids for r in got] == [r.token_ids for r in want]
    assert [r.finished for r in got] == [r.finished for r in want]
    assert [r.text for r in got] == [r.text for r in want]
    for i, (res, ref) in enumerate(zip(got, want)):
        assert len(res.trajectory) == len(ref.trajectory)
        for j, (e, r) in enumerate(zip(res.trajectory, ref.trajectory)):
            _same(e, r, f"row {i} entry {j}", atol)
    steps = want_seen.decodes()
    assert [o["flavour"] for o in seen.opened] == \
        ["gen.prefill"] + ["gen.decode"] * bool(steps)
    if not steps:
        return {"out": got, "steps": steps, "done": None}
    first = next(o for o in want_seen.opened if o["flavour"] == "gen.decode")
    assert seen.opened[1]["tokens_real"] == first["tokens_real"]
    done = seen.closed[1]
    assert done["forwards"] == len(steps)
    for fact in ("committed_tokens", "drafted", "accepted"):
        assert (fact in done) == (fact in steps[0]), fact
        if fact in done:
            assert done[fact] == sum(s[fact] for s in steps), fact
    for fact in ("load", "keys"):
        if steps[0].get(fact) is None:
            assert done.get(fact) is None, fact
        else:
            np.testing.assert_allclose(
                done[fact], np.concatenate([s[fact] for s in steps]),
                atol=atol, err_msg=fact)
    mine, its = jax.device_get((loop.cache, per_step.cache))
    assert jax.tree.structure(mine) == jax.tree.structure(its)
    for n, (a, b) in enumerate(zip(jax.tree.leaves(mine),
                                   jax.tree.leaves(its))):
        _same(a, b, f"cache leaf {n}", atol)
    return {"out": got, "steps": steps, "done": done}


# -- the cases every model kind is put through -----------------------------------------

CASES = ("budget_1", "budget_2", "whole_budget",
         "rows_end_at_different_steps", "a_padding_row")


def check_case(case: str, gen: GreedyGenerator, texts, new: int,
               atol: float = 1e-5, **kw) -> Dict[str, Any]:
    """``case`` of ``CASES`` on ``gen`` (at least two ``texts``, ``new``
    tokens of at least five): the comparison above, and what the case is
    about.  A budget of 1 or 2 is ``warm()``'s way of asking (``_steps``:
    the shapes of ``new`` tokens, fewer of them)."""
    gen.eos_token_ids = set()
    encs = [gen.tokenizer.encode(t) for t in texts]
    kw = dict(kw, encodings=encs, padded_rows=len(encs),
              bucket=-(-max(len(e) for e in encs) // 32) * 32)
    compare = assert_the_loop_gives_what_the_hosts_loop_gave
    if case in ("budget_1", "budget_2"):
        budget = int(case[-1])
        seen = compare(gen, texts, new, atol, _steps=budget, **kw)
        assert [len(r.token_ids) for r in seen["out"]] == [budget] * len(encs)
        assert len(seen["steps"]) == budget - 1
        return seen
    if case == "whole_budget":
        seen = compare(gen, texts, new, atol, **kw)
        assert [len(r.token_ids) for r in seen["out"]] == [new] * len(encs)
        assert not any(r.finished for r in seen["out"])
        return seen
    base = [r.token_ids for r in
            per_step_twin(gen).generate(texts, new, **kw)]
    if case == "rows_end_at_different_steps":
        def cut(row, eos):  # the tokens kept before the first of ``eos``
            return next((i for i, t in enumerate(row) if t in eos), new)

        # an id of the first row and one of the last that end the two at
        # different steps, neither at the prefill's token
        gen.eos_token_ids = next(
            {a, b} for a in base[0][1:] for b in base[-1][1:]
            if 0 < cut(base[0], {a, b}) != cut(base[-1], {a, b}) > 0)
        seen = compare(gen, texts, new, atol, **kw)
        gen.eos_token_ids = set()
        ends = [len(r.token_ids) for r in seen["out"]]
        assert seen["out"][0].finished and seen["out"][-1].finished
        assert 0 < ends[0] != ends[-1] > 0 and max(ends[0], ends[-1]) < new
        for r, b in zip(seen["out"], base):
            assert r.token_ids == b[:len(r.token_ids)]
        return seen
    assert case == "a_padding_row", case
    seen = compare(gen, texts, new, atol,
                   **dict(kw, padded_rows=len(encs) + 1))
    assert [r.token_ids for r in seen["out"]] == base
    return seen
