"""`serve-closed-model`'s loop, server, window and tie-aware verdict for
Qwen3-Next-80B-A3B-Instruct served as one chip of a v5e-32 (64 of 512 routed experts, 18,992
of 151,936 vocabulary rows, 12 of 48 layers: nine of a Gated-DeltaNet mixer whose per-slot
state is a cache group of its own, three of gated grouped-query attention of head size 256).
As the other models' modes do, this file loads a copy of that mode of its OWN and sets in the
copy (in memory; the file on disk is Moonlight's and is not touched) the architecture's
builder and reference, this model's verdict, and a by-scope reduction of the trace:
`lib/stage_times.reduce_path(path, stages=STAGES + ("gdn/*",))`, handed on in the tables'
form that the accepted readers of `run["scopes"]` read (no copy of `scope_reduce`). The
builder is imported HERE, at the top, so a checkout whose program lacks the model fails at
once.

What is checked: prompt + served tokens of SIX greedy requests served in full in the window,
through `reference/qwen3_next_ref.sequence_logits` in float32, TOKEN BY TOKEN through the
recurrence, plain softmax attention, with the SAME held range and vocabulary slice: every
served token's reference logit against its position's largest (the deficit). Two of the six
have the longest prompts the seed's greedy requests offer (16,384 tokens where the window
holds one), two the longest answers, two a prompt SHORTER than its bucket (only there do the
state and the history at `real_len` differ from those at the bucket's end; three of the
eight prompt lengths are such). And FOUR sampled requests (limit 5), the hand-over of five
prompts the engine admits behind the window (limit 6), the served state one step on (limit 4)
and the prompts' scans (limit 7).

THE LOGITS are y W_head with y of unit RMS over 2,048 values times (1 + w) and W_head
normal(0, 0.02): standard deviation about 0.94, the size of Kimi-Linear's and Moonlight's, so
the token margins are theirs and not granite's sixteenths. The head is untied: a greedy
request does not repeat its prompt's last token, and greedy tokens judge this model.

THE VERDICT, four limits on the served tokens and three numbers read where they lie (the
readings that place each constant: PERF.md section 6, PR 56; each lies between the served
runs' worst reading and the least reading of the WRONG programs it is there to tell;
`reference/qwen3_next_ref.WRONG` lists them). The served program computes in bfloat16 through
12 layers with 10 picks of 512 a token a layer, so a share of its picks lie a little under
the reference's best logit whatever the router does: each token limit is a SHARE, and a
wrong program whose effect is below that noise cannot be told by tokens at all (a state kept
in bfloat16: limit 4 is a NUMBER for it; a scan on bfloat16 operands: limit 7).

  1. EARLY. Of the positions among a request's first EARLY = 96 generated ones whose picks
     are PICK_GAP clear of a tie in every expert layer, the share within LOGIT_MARGIN of the
     reference's best logit, REQUEST BY REQUEST (those with at least MIN_JUDGED of them): the
     LEAST is at least MIN_JUDGED_WITHIN. The limit that reads the hand-over from the
     prefill.
  2. ALL. Of ALL checked positions at least MIN_SHARE_WITHIN within SHARE_MARGIN: the
     backstop that reads every position served.
  3. LATE. Of the LAST LATE = 64 positions of the two longest answers checked (position
     ~1,000 of generation: the state has been decayed and rewritten a thousand times), ALL of
     them: at least MIN_LATE_WITHIN within LATE_MARGIN.
  4. STATE STEP. The recurrence AS SERVED, on the blocks it served: behind the window,
     STATE_STEP_SLOTS state blocks of every Gated-DeltaNet layer, as the timed run left them
     in the engine's own arena (its type with them), are moved ONE position on by the
     program's own step (`models/qwen3_next.gdn_step_inputs`, then `gdn_state_update` on the
     path the chunk program took: the kernel on the chip) from the checked requests' last
     tokens, and the block that comes back is held against the reference's float32 `gdn_step`
     on that block and the program's own q, k, v, g, beta: the largest relative error
     (Frobenius, a slot a layer) is at most MAX_STATE_STEP_ERROR. Beside it stands the same
     step with the state kept in bfloat16 (the reference's own, rounded before and after):
     the reading of the wrong program `state_bf16`, which every token limit passes.
  5. SAMPLED. Of ALL generated positions of SAMPLED_REQUESTS sampled requests (temperature
     0.8; the longest answers the window's sampled requests offer, two of them with a prompt
     shorter than its bucket), the share whose served token lies within SAMPLED_MARGIN of the
     position's largest PERTURBED reference logit (logits / T + the request's own Gumbel
     draws, rebuilt from its seed by the reference's own threefry and Gumbel transform over
     the sampler's counters: `reference/granite_hybrid_ref.gumbel_draws`, which
     `tests/test_serve_closed_qwen3_next.py` holds against the engine's sampler position by
     position) is at least MIN_SAMPLED_WITHIN.
  6. HAND-OVER, from the ENGINE'S OWN compiled prefill programs and arena. Behind the window
     (the server down, its drivers joined) the engine's scheduler admits ONE seeded prompt a
     bucket of the cell (2,048, 4,096, 8,192, 12,288, 16,384), each three quarters of its
     bucket and 17 rows long (off a chunk's edge; the same lengths on every seed, so the
     reference's pieces compile once a cache), the largest over 192 chunks of the scan's
     carry, through `scheduler.admit`: the jitted programs that prefilled every prompt
     of the window (an admission that compiles anything fails the run: `handover_program`).
     What each wrote INTO THE ENGINE'S ARENA for its slot (each Gated-DeltaNet layer's state
     block and history block, each attention layer's K | V rows, found through the slot's
     page row) is held against what the float32 reference leaves behind at the prompt's last
     row, token by token: the largest relative error (Frobenius, a layer, any bucket) is at
     most MAX_HANDOVER_ERROR.
  7. SCAN. Limit 6's floor is the served program's bfloat16 ACTIVATIONS through twelve layers,
     so a prompt's scan whose PRODUCTS ran below the stated float32 would pass it (and limit
     4 reads the decode step alone). So the scan is read in limit 4's form: for the same five
     prompts and every Gated-DeltaNet layer, the program's own operands
     (`models/qwen3_next.gdn_prompt_inputs`: q, k, v, g and beta with 0 past the prompt, at
     the bucket's padded shape) go through the program's `gdn_scan` BY THE PATH THE BUCKET'S
     PREFILL TOOK (the kernel ops/kda_chunk.py on the chip), and the state and the prompt's
     rows that come back are held against the reference's float32 recurrence TOKEN BY TOKEN
     on the same operands: the largest relative error (Frobenius) is at most MAX_SCAN_ERROR.
     Beside it stands that recurrence with its operands rounded to bfloat16: the reading of
     the wrong program `scan_bf16`, which every other limit passes.

THE READINGS THAT PLACE THE CONSTANTS (my chip runs, PR 56, before the limits were set: the
served program on seeds 2147483999 and 2200000077, the eight WRONG programs on the second):
limit 1, the least request's share within 0.03: served 0.877 / 0.904, `no_output_gate` 0.48,
`full_rotary` 0.86, the other four 0.0-0.01 (limit 0.65); limit 2, within 0.1: served 0.986 /
0.985, `no_output_gate` 0.600, `full_rotary` 0.975, the others 0.0002-0.052 (limit 0.85);
limit 3: served 0.992 / 1.0, `no_output_gate` 0.695, the others 0.0-0.055 but `full_rotary`
1.0 (limit 0.85); limit 4: served 2.8e-8 / 2.6e-8, a state kept in bfloat16 2.26e-3 / 2.27e-3
(limit 1e-5); limit 5, within 0.05 of the largest perturbed logit: served 0.9854 / 0.9797 (the
largest deficit 0.26 / 0.25: bfloat16 through twelve layers of ten picks of 512),
`no_output_gate` 0.856, `full_rotary` 0.9785, the others 0.37-0.54 (limit 0.93); limit 6:
served 0.0870 / 0.0828 (state 0.072-0.087 at every bucket, history 0.041-0.063, K | V rows
0.055-0.058; the five prompts' admissions added no executable), `no_output_gate` 0.372,
`full_rotary` 0.739, the other four 1.04-1.42 (limit 0.16: 1.8 times the served, 2.3 under
the least wrong); limit 7: the kernel's scan 2.5e-4 to 4.7e-4, rising with the bucket (3.6e-4 /
4.7e-4 at 16,384 rows over 192 chunks of float32 carry), operands in bfloat16 2.93e-3 /
2.95e-3 (limit 1.2e-3: 2.5 times either way). So `full_rotary` (192 more of 256 values
rotated) is told by limit 6 ALONE, by a factor 4.6: under seeded weights the served tokens do
not feel it, the cached keys do. All eight were `not correct` on that seed.

QWEN3_NEXT_WRONG_REFERENCE (a builder's facility, unset in every measured run): a comma list
of `reference/qwen3_next_ref.WRONG` names, or `all`. For each, the tokens that WRONG program
picks along the checked sequences are judged against the true reference by the same limits
(its hand-over behind the two smallest buckets alone: a reference pass a name a bucket), and
the readings go to the facts line under `wrong_references`; `state_bf16` and `scan_bf16`,
which a NUMBER tells, get the served tokens' readings with their own number in its place; the
run's `correct` is not touched. QWEN3_NEXT_DUMP_READINGS=<file.npz> keeps every judged
reading."""

import importlib.util
import os
import time

import numpy as np

from lib import qwen3_next as _builder  # noqa: F401  (fails at once without the model)

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _own_copy(folder, file_name, module_name):
    spec = importlib.util.spec_from_file_location(
        module_name, os.path.join(BENCH, folder, file_name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


base = _own_copy("modes", "serve-closed-model", "bench_modes_serve_closed_model_qwen3_next")
base.ARCHITECTURES["qwen3_next"] = ("qwen3_next", "qwen3_next_ref")

# the Mosaic kernels this model's programs call, by the names they carry in a trace
KERNELS = ("paged_attention_grouped", "_causal_rows_call", "grouped_swiglu", "routed_combine",
           "kda_step", "kda_chunk")


class StageTables:
    """`lib/stage_times.py`'s tables of a trace directory, with this model's `gdn/*`
    among the stages, in the form the readers of `run["scopes"]` read: {program:
    {"scopes": {stage: s}, "kernels": {kernel: s}}}."""

    @staticmethod
    def reduce_dir(trace_dir):
        from lib import stage_times, trace_reduce

        path = trace_reduce.find_xplane(trace_dir)
        tables = path and stage_times.reduce_path(
            path, stages=stage_times.STAGES + ("gdn/*",))
        if not tables:
            return None
        out = {"busy_s": tables["busy_s"]}
        for program, entry in tables["modules"].items():
            kernels = {}
            for kinds in entry["kinds"].values():
                for kind, seconds in kinds.items():
                    if kind in KERNELS:
                        kernels[kind] = kernels.get(kind, 0.0) + seconds
            out[program] = {"scopes": dict(entry["stages"]), "kernels": kernels}
        return out


class _Scopes:
    """What `base.run` asks of its `scope_reduce`: the tables, without the one entry
    that is no program's."""

    @staticmethod
    def reduce_dir(trace_dir):
        tables = StageTables.reduce_dir(trace_dir)
        if tables is not None:
            _Scopes.busy_s = tables.pop("busy_s")
        return tables


base.scope_reduce = _Scopes

# The logits are y W_head with y of unit RMS over 2048 values times (1 + w), w uniform in
# +-0.5, and W_head normal(0, 0.02): standard deviation about 0.94. The readings that place
# the constants: PERF.md section 6, PR 56.
base.LOGIT_MARGIN = 0.03
base.PICK_GAP = 0.0005      # in the router's logits: the 10th of 512 leads the 11th
base.MIN_SHARE_WITHIN = 0.85
base.CHECKED_REQUESTS = 6
# sequences are checked padded to a multiple of this (the longest is 17,408): the
# reference's pieces compile for at most three lengths, and 18,432 float32 rows fit beside
# the weights and the pools
base.PAD_TO = 6144
EARLY = 96                  # limit 1 judges a request's first generated positions
MIN_JUDGED = 16             # a request with fewer judged positions is not read by limit 1
MIN_JUDGED_WITHIN = 0.65
SHARE_MARGIN = 0.1          # the margin of limit 2
LATE = 64                   # limit 3 judges the last positions of the longest answers
LATE_ANSWERS = 2
LATE_MARGIN = 0.1
MIN_LATE_WITHIN = 0.85
CHUNK = 64                  # rows a chunk of the prompt's scan (models/_delta.KDA_CHUNK)
# the builder's facility reads the WRONG programs' hand-over behind this many of the
# smallest buckets (a reference pass a name a bucket)
WRONG_HANDOVER_BUCKETS = 2
STATE_STEP_SLOTS = 8        # limit 4 steps this many state blocks of every recurrent layer
MAX_STATE_STEP_ERROR = 1e-5
MAX_HANDOVER_ERROR = 0.16   # limit 6: what a prefill leaves behind at a prompt's last row
MAX_SCAN_ERROR = 1.2e-3     # limit 7: a prompt's chunked scan against the token-by-token one
SAMPLED_REQUESTS = 4        # limit 5 judges this many sampled requests
SAMPLED_MARGIN = 0.05       # in logits / T, T = 0.8: perturbed logits, top two ~1 apart
MIN_SAMPLED_WITHIN = 0.93
# the WRONG programs that a NUMBER tells (limits 4 and 7) and no served token could: the
# builder's facility does not run the reference along the checked sequences for them
TOLD_BY_NUMBER = ("state_bf16", "scan_bf16")


class Qwen3NextServed(base.ModelServed):
    last_stats = {}       # of the newest server of this process, for `run`

    def shutdown(self):
        # the pools' peaks and the paths that ran, read before the engine goes
        Qwen3NextServed.last_stats = self.engine.stats()
        super().shutdown()

    def request(self, spec, due, measured=True):
        """`lib/serving.Served.request` with a SAMPLED request's tokens, seed and
        temperature kept in the record too (`sampled`; `output` stays the greedy
        requests')."""
        body = {"prompt": base.traffic_lib.prompt_tokens(
            self.ctx.seed, spec["index"], spec["prompt_len"], self.cfg["vocab_size"]),
            "max_new_tokens": spec["max_new_tokens"]}
        if spec["temperature"]:
            body.update(temperature=spec["temperature"], seed=spec["seed"])
        sent = time.monotonic()
        reply = base.serving.sse.generate(self.port, body, base.serving.REQUEST_TIMEOUT_S)
        done = reply["done"]
        greedy = not spec["temperature"]
        record = {
            "index": spec["index"], "due": due, "sent": sent,
            "first": reply["first"], "last": reply["last"], "end": reply["end"],
            "tokens": len(reply["tokens"]), "prompt_len": spec["prompt_len"],
            "max_new_tokens": spec["max_new_tokens"], "greedy": greedy,
            "status": reply["status"],
            "queue_wait": (done.get("metrics") or {}).get("queue_wait"),
            "ok": (reply["status"] == 200 and reply["error"] is None
                   and len(reply["tokens"]) == spec["max_new_tokens"]
                   and done.get("finish_reason") in ("length", "stop")),
            "error": reply["error"], "measured": measured,
            "output": reply["tokens"] if greedy else None,
            "sampled": None if greedy else {"tokens": reply["tokens"], "seed": spec["seed"],
                                            "temperature": spec["temperature"]},
        }
        with self._lock:
            self.records.append(record)
        return record

    def _bucket(self, prompt_len):
        return min(b for b in self.sizes["prefill_buckets"] if b >= prompt_len)

    def _chosen(self, measured):
        """Eight of the greedy requests served in full, by the seed: two of the longest
        prompts served, two of the longest answers, two whose prompt is shorter than its
        bucket, the rest from the others."""
        greedy = [r for r in measured if r["ok"] and r["greedy"] and r["output"]]
        rng = np.random.default_rng([int(self.ctx.seed), 56])
        chosen = []

        def take(wanted, n):
            pool = [r for r in greedy if r not in chosen and wanted(r)]
            n = max(0, min(n, len(pool), base.CHECKED_REQUESTS - len(chosen)))
            if n:
                chosen.extend(pool[int(i)] for i in rng.choice(len(pool), size=n,
                                                               replace=False))

        if greedy:
            longest = max(r["prompt_len"] for r in greedy)
            answer = max(len(r["output"]) for r in greedy)
            take(lambda r: r["prompt_len"] == longest, 2)
            take(lambda r: len(r["output"]) == answer,
                 2 - sum(len(r["output"]) == answer for r in chosen))
            take(lambda r: self._bucket(r["prompt_len"]) > r["prompt_len"],
                 2 - sum(self._bucket(r["prompt_len"]) > r["prompt_len"] for r in chosen))
            take(lambda r: True, base.CHECKED_REQUESTS)
        return chosen

    def _chosen_sampled(self, measured):
        """SAMPLED_REQUESTS of the sampled requests served in full, by the seed: two whose
        prompt is shorter than its bucket, the rest the longest answers offered."""
        pool = [r for r in measured if r["ok"] and r.get("sampled")]
        rng = np.random.default_rng([int(self.ctx.seed), 5605])
        order = [pool[int(i)] for i in rng.permutation(len(pool))]
        order.sort(key=lambda r: -len(r["sampled"]["tokens"]))
        padded = [r for r in order if self._bucket(r["prompt_len"]) > r["prompt_len"]][:2]
        rest = [r for r in order if r not in padded]
        return (padded + rest)[:SAMPLED_REQUESTS]

    def _sampled_deficits(self, chosen, names):
        """Limit 5's readings: {program: [a request's deficits]}, the picks' gaps and the
        count of lanes drawn on the top of the sampler's lattice, for the served tokens and
        for the tokens each WRONG program of `names` draws along the served sequence under
        the same Gumbel draws."""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def under(z, picked):
            return z.max(-1) - jnp.take_along_axis(z, picked[:, None], -1)[:, 0]

        deficits, gaps, top_lanes = {name: [] for name in ("served",) + names}, [], 0
        for r in chosen:
            tokens, temp = r["sampled"]["tokens"], float(r["sampled"]["temperature"])
            prompt = base.traffic_lib.prompt_tokens(self.ctx.seed, r["index"], r["prompt_len"],
                                                    self.cfg["vocab_size"])
            seq = prompt + tokens
            seq = seq + [0] * (-len(seq) % base.PAD_TO)
            rows = np.arange(r["prompt_len"] - 1, r["prompt_len"] - 1 + len(tokens))
            true, gap = self.reference.sequence_logits(self.params, self.cfg, seq, rows, gaps=True)
            # the reference's OWN threefry and Gumbel transform over the sampler's counters
            # and float32 lattice (`top`: the lanes on the lattice's top, where the engine's
            # log(u) reads +inf and it picks the lane whatever the logits, once in 2^24)
            noise, top = self.reference.gumbel_draws(
                np.uint32(int(r["sampled"]["seed"]) & 0xFFFFFFFF), true)
            top_lanes += top
            z = true / temp + noise
            deficits["served"].append(np.asarray(under(z, jnp.asarray(tokens, jnp.int32))))
            gaps.append(np.asarray(gap))
            for name in names:
                wrong = self.reference.sequence_logits(
                    self.params, self.cfg, seq, rows, wrong=name, prompt_len=r["prompt_len"],
                    bucket=self._bucket(r["prompt_len"]))
                drawn = jnp.argmax(wrong / temp + noise, -1).astype(jnp.int32)
                deficits[name].append(np.asarray(under(z, drawn)))
        return deficits, gaps, top_lanes

    def check_outputs(self, measured):
        """The module docstring's three limits over `_chosen`'s requests. The reference
        runs once a request (and once more for each WRONG program asked for)."""
        chosen = self._chosen(measured)
        facts = {"checked": 0, "max_logit_deficit": None, "logit_deficits": [],
                 "logit_margin": base.LOGIT_MARGIN, "pick_gap": base.PICK_GAP}
        if not chosen:
            return False, facts
        wrong = os.environ.get("QWEN3_NEXT_WRONG_REFERENCE", "")
        asked = self.reference.WRONG if wrong == "all" else tuple(filter(None, wrong.split(",")))
        names = tuple(name for name in asked if name not in TOLD_BY_NUMBER)
        deficits, gaps, stds = {name: [] for name in ("served",) + names}, [], []
        for r in chosen:
            prompt = base.traffic_lib.prompt_tokens(self.ctx.seed, r["index"], r["prompt_len"],
                                                    self.cfg["vocab_size"])
            seq = prompt + r["output"]
            seq = seq + [0] * (-len(seq) % base.PAD_TO)
            rows = np.arange(r["prompt_len"] - 1, r["prompt_len"] - 1 + len(r["output"]))
            true, gap = self.reference.sequence_logits(self.params, self.cfg, seq, rows, gaps=True)
            true = np.asarray(true)
            under = lambda picked: true.max(-1) - true[np.arange(len(rows)), picked]
            deficits["served"].append(under(np.asarray(r["output"])))
            gaps.append(np.asarray(gap))
            stds.append(float(true.std()))
            for name in names:
                # the tokens a WRONG program picks along the served sequence
                deficits[name].append(under(np.asarray(self.reference.sequence_logits(
                    self.params, self.cfg, seq, rows, wrong=name, prompt_len=r["prompt_len"],
                    bucket=self._bucket(r["prompt_len"]))).argmax(-1)))
        clear = [g >= base.PICK_GAP for g in gaps]
        request = np.concatenate([np.full(len(c), i) for i, c in enumerate(clear)])
        early = np.concatenate([np.arange(len(c)) < EARLY for c in clear]) & np.concatenate(clear)
        # the last LATE positions of the longest answers checked
        order = sorted(range(len(chosen)), key=lambda i: -len(chosen[i]["output"]))
        tail = set(order[:LATE_ANSWERS])
        late = np.concatenate([(np.arange(len(c)) >= len(c) - LATE) if i in tail
                               else np.zeros(len(c), bool) for i, c in enumerate(clear)])
        step = state_step_readings(
            _builder.program, self.reference, self.model_cfg, self.params,
            self.engine.kv.arena, [r["output"][-1] for r in chosen], self.ctx.seed)
        told_by_state = {"served": step["error"], "state_bf16": step["error_state_bf16"]}
        drawn = self._chosen_sampled(measured)
        sampled, sampled_gaps, top_lanes = self._sampled_deficits(drawn, names)
        # behind limit 4, which reads blocks as the WINDOW left them: the hand-over admits
        # prompts of its own into the engine's arena
        hand = handover_readings(self.engine, self.reference, self.cfg, self.params,
                                 self.ctx.seed, CHUNK, names)
        left_behind = dict(hand["wrong"], served=hand["error"])
        scan = scan_readings(_builder.program, self.reference, self.model_cfg, self.params,
                             hand["prompts"])
        told_by_scan = {"served": scan["error"], "scan_bf16": scan["error_products_bf16"]}
        read = {name: _limits(np.concatenate(parts), early, late, request,
                              told_by_state.get(name),
                              np.concatenate(sampled[name]) if drawn else None,
                              left_behind[name], told_by_scan.get(name))
                for name, parts in deficits.items()}
        for name in asked:
            if name in TOLD_BY_NUMBER:
                # the served tokens' own readings with this program's number in place of
                # the served one: what the verdict would read of a program that differs
                # from the served one in that number alone
                read[name] = _limits(
                    np.concatenate(deficits["served"]), early, late, request,
                    told_by_state.get(name, step["error"]),
                    np.concatenate(sampled["served"]) if drawn else None, hand["error"],
                    told_by_scan.get(name, scan["error"]))
        del hand["prompts"]
        if hand["executables_added"]:
            # an admission behind the window compiled: not the window's program
            read["served"]["fails"].append("handover_program")
        dump = os.environ.get("QWEN3_NEXT_DUMP_READINGS")
        if dump:
            np.savez(dump, gaps=np.concatenate(gaps), early=early, late=late, request=request,
                     lengths=np.asarray([len(c) for c in clear]),
                     sampled_gaps=np.concatenate(sampled_gaps) if drawn else np.zeros(0),
                     sampled_lengths=np.asarray([len(r["sampled"]["tokens"]) for r in drawn]),
                     sampled_prompt_lens=np.asarray([r["prompt_len"] for r in drawn]),
                     **{"sampled_" + name: np.concatenate(parts)
                        for name, parts in sampled.items() if drawn},
                     prompt_lens=np.asarray([r["prompt_len"] for r in chosen]),
                     **{name: np.concatenate(parts) for name, parts in deficits.items()})
        padded = sum(self._bucket(r["prompt_len"]) > r["prompt_len"] for r in chosen)
        facts.update(read["served"], checked=len(chosen),
                     logit_deficits=[float(d.max()) for d in deficits["served"]],
                     early=EARLY, min_judged_within=MIN_JUDGED_WITHIN,
                     share_margin=SHARE_MARGIN, min_share_within=base.MIN_SHARE_WITHIN,
                     late=LATE, late_margin=LATE_MARGIN, min_late_within=MIN_LATE_WITHIN,
                     state_step=step, max_state_step_error=MAX_STATE_STEP_ERROR,
                     handover=hand, max_handover_error=MAX_HANDOVER_ERROR,
                     scan=scan, max_scan_error=MAX_SCAN_ERROR,
                     sampler_top_lattice_lanes=top_lanes,
                     sampled_margin=SAMPLED_MARGIN, min_sampled_within=MIN_SAMPLED_WITHIN,
                     sampled_checked=len(drawn),
                     sampled_prompt_lens=sorted(r["prompt_len"] for r in drawn),
                     logit_std=max(stds),
                     checked_prompt_lens=sorted(r["prompt_len"] for r in chosen),
                     checked_answer_lens=sorted(len(r["output"]) for r in chosen),
                     padded_prompts_checked=padded)
        if asked:
            facts["wrong_references"] = {name: read[name] for name in asked}
        return not read["served"]["fails"], facts


def _normed_rows(cfg, lp, wte, tokens):
    """The first layer's normed input rows of `tokens`, by the family's zero-centred norm,
    in the served type: what the program's own mixer pieces are given."""
    import jax
    import jax.numpy as jnp

    x = wte[tokens].astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + cfg.rms_eps)
            * (1.0 + lp["norm1"].astype(jnp.float32))).astype(wte.dtype)


MIXER = ("norm1", "w_qkvz", "w_ba", "conv_w", "dt_bias", "a_log")


def state_step_readings(program, reference, cfg, params, arena, tokens, seed):
    """Limit 4's readings. `arena` is the engine's (attention rows, state, history) as the
    run left it; `tokens` are cycled over STATE_STEP_SLOTS slots, whose blocks the seed draws
    among those a slot can hold (block 0 is scratch). For each Gated-DeltaNet layer the drawn
    blocks go, in the arena's own type, into an arena of that one layer, the program's
    `gdn_step_inputs` makes q, k, v, g, beta from the tokens' normed embedding rows and the
    blocks' histories, and `gdn_state_update` moves the states one position on by the path
    the served step takes. Returns {"error": the largest relative error of a slot's new
    state against `reference.gdn_step` in float32 on the same block and operands,
    "error_state_bf16": the LEAST such error of that reference with its state kept in
    bfloat16, "path", "slots", "layers", "state_dtype"}."""
    import jax
    import jax.numpy as jnp

    _, state, conv = arena
    n = min(STATE_STEP_SLOTS, state.shape[2] - 1)
    rng = np.random.default_rng([int(seed), 5654])
    blocks = np.concatenate([[0], 1 + rng.choice(state.shape[2] - 1, size=n, replace=False)])
    tokens = jnp.asarray([tokens[i % len(tokens)] for i in range(n)], jnp.int32)
    ids = jnp.arange(1, n + 1, dtype=jnp.int32)
    path = program.recurrence_path(cfg)
    f32 = jnp.float32

    @jax.jit
    def step(lp, wte, st, cv):
        u = _normed_rows(cfg, lp, wte, tokens)
        arenas = {program.GDN: st, program.CONV: cv}
        q, k, v, g, beta, _, arenas = program.gdn_step_inputs(cfg, lp, u, arenas, 0, ids, None)
        _, arenas = program.gdn_state_update(arenas, 0, ids, None, q, k, v, g, beta, path)
        before, after = st[0, 0, ids].astype(f32), arenas[program.GDN][0, 0, ids].astype(f32)
        # the decay is a scalar a head: the program hands it over a head's key channels
        one = jax.vmap(reference.gdn_step)
        true = one(before, q, k, v, g[..., 0], beta)[0]
        kept_low = reference.as_bfloat16(
            one(reference.as_bfloat16(before), q, k, v, g[..., 0], beta)[0])
        size = lambda a: jnp.sqrt(jnp.sum(a * a, (1, 2, 3)))
        return size(after - true) / size(true), size(kept_low - true) / size(true)

    errors, lows = [], []
    for li, lp in enumerate(params["layers"]):
        if cfg.kind(li) == program.LINEAR:
            lg = cfg.index_in_group(li)
            error, kept_low = step({k: lp[k] for k in MIXER}, params["wte"],
                                   state[lg:lg + 1, :, blocks], conv[lg:lg + 1, :, blocks])
            errors.append(np.asarray(error))
            lows.append(np.asarray(kept_low))
    return {"error": float(np.max(errors)), "error_state_bf16": float(np.min(lows)),
            "path": path, "slots": n, "layers": len(errors), "state_dtype": str(state.dtype)}


def handover_readings(engine, reference, cfg, params, seed, chunk, names=()):
    """Limit 6's readings, from the ENGINE'S OWN compiled prefill programs and arena. Behind
    the window (the server is down, its driver threads joined) the engine's scheduler admits
    one seeded prompt a bucket of the cell, each ending past the middle of its bucket and
    off a chunk's edge (the largest bucket's over several chunks of the scan), by the call
    that admitted every request of the window (`scheduler.admit`: the same jitted program
    a bucket, so the same executable; `executables_added` says how many it had to compile,
    0), and what the prefill wrote into the engine's arena for that slot (each Gated-DeltaNet
    layer's state block and history block, the attention layer's K | V rows) is held
    against what the float32 reference leaves behind at the prompt's last row, token by
    token. Returns {"by_bucket": {bucket: {"prompt_len", "state", "history", "rows": the
    largest relative error, a layer}}, "error": the largest of them all; "wrong": {name:
    that reading of the WRONG reference `name` against the true one, the largest over the
    same prompts}; "prompts": [(bucket, tokens)] for the scan limit; "seconds"}."""
    sched, kv = engine.scheduler, engine.kv
    page = kv.block_size
    starts = [g.start for g in kv.group_layout]
    rng = np.random.default_rng([int(seed), 5606])
    f32 = lambda a: np.asarray(a, np.float32)
    compiled, began = 0, time.monotonic()

    def errors(got, want):
        def size(a):
            return np.sqrt((a.reshape(a.shape[0], -1) ** 2).sum(-1))
        return {kind: float(np.max(size(got[kind].reshape(want[kind].shape) - want[kind])
                                   / size(want[kind]))) for kind in want}

    by_bucket, wrong, prompts, below = {}, {}, [], 0
    for bucket in sorted(sched.buckets):
        # three quarters of the bucket and 17 rows: past the bucket below, off a chunk's
        # edge, and the SAME length on every seed, so that the reference's pieces for it
        # compile once a cache (the tokens are the seed's)
        real = min(bucket - 1, max(below + 1, 3 * bucket // 4 + 17))
        real += real % chunk == 0
        below = bucket
        prompt = rng.integers(0, cfg["vocab_size"], real).astype(np.int32)
        held = {slot for slot in range(kv.num_slots) if kv.length(slot)}
        probe, executables = object(), sched.compile_count
        if sched.admit(probe, prompt, 1) is None:
            raise RuntimeError(f"no slot or pages free behind the window: {kv.occupancy()}")
        compiled += sched.compile_count - executables
        slot, = (s for s in range(kv.num_slots) if kv.length(s) and s not in held)
        row = np.asarray(kv.page_table[slot])
        arena = kv.arena
        pages = row[starts[0]:starts[0] + -(-real // page)]
        rows = f32(arena[0][:, 0, pages])             # (layers, pages, kv, page, 2d)
        rows = rows.transpose(0, 1, 3, 2, 4).reshape(rows.shape[0], -1, rows.shape[2],
                                                     rows.shape[4])[:, :real]
        served = {"state": f32(arena[1][:, 0, row[starts[1]]]),
                  "history": f32(arena[2][:, 0, row[starts[2]]]), "rows": rows}
        sched.cancel(probe)

        def left_behind(name=None):
            cache = {}
            reference.sequence_logits(params, cfg, prompt.tolist(), rows=[real - 1],
                                      wrong=name, prompt_len=real, bucket=bucket, cache=cache)
            return {kind: np.stack([np.asarray(a) for a in parts])
                    for kind, parts in cache.items()}

        true = left_behind()
        by_bucket[bucket] = dict(errors(served, true), prompt_len=real)
        for name in names if len(by_bucket) <= WRONG_HANDOVER_BUCKETS else ():
            wrong[name] = max(wrong.get(name, 0.0), *errors(left_behind(name), true).values())
        prompts.append((bucket, prompt))
    return {"by_bucket": by_bucket, "wrong": wrong, "prompts": prompts,
            "seconds": time.monotonic() - began,
            "error": max(read[kind] for read in by_bucket.values()
                         for kind in ("state", "history", "rows")),
            "executables_added": compiled}


def scan_readings(program, reference, cfg, params, prompts):
    """Limit 7's readings. For each (bucket, tokens) of `prompts` and each Gated-DeltaNet
    layer, the program's own operands of a prompt's scan
    (`models/qwen3_next.gdn_prompt_inputs` on the tokens' normed embedding rows padded to
    the bucket: q, k, v, g and beta with 0 past the prompt) go through the program's
    `gdn_scan` at the bucket's shape BY THE PATH THE BUCKET'S PREFILL TOOK
    (`prefill_recurrence_path`: the kernel ops/kda_chunk.py on the chip), and the state and
    the prompt's rows that come back are held against the reference's float32 recurrence
    TOKEN BY TOKEN on the same operands. Returns {"error": the largest relative error
    (Frobenius) of a state or a prompt's rows, "error_products_bf16": the LEAST such error of
    the reference's recurrence with its operands rounded to bfloat16 (the reading of a scan
    whose products run below float32: the wrong program `scan_bf16`), "by_bucket": {bucket:
    the largest error}, "paths": {bucket: path}, "layers"}."""
    import jax
    import jax.numpy as jnp

    size = lambda a: jnp.sqrt(jnp.sum(a * a))

    @jax.jit
    def scan(lp, wte, tokens, real):
        u = _normed_rows(cfg, lp, wte, tokens)
        q, k, v, g, beta, _, _ = program.gdn_prompt_inputs(cfg, lp, u, real)
        path = program.prefill_recurrence_path(cfg, tokens.shape[0])
        o, S, _ = program.gdn_scan(q, k, v, g, beta, real, path)
        o_true, S_true = reference.gdn_recurrence(q, k, v, g[..., 0], beta)
        o_low, S_low = reference.gdn_recurrence(
            *(reference.as_bfloat16(a) for a in (q, k, v, g[..., 0], beta)))
        keep = (jnp.arange(tokens.shape[0]) < real)[:, None, None]
        rel = lambda S, o: jnp.maximum(size(S - S_true) / size(S_true),
                                       size(jnp.where(keep, o - o_true, 0.0))
                                       / size(jnp.where(keep, o_true, 0.0)))
        return rel(S, o), rel(S_low, o_low)

    layers = [{k: lp[k] for k in MIXER} for li, lp in enumerate(params["layers"])
              if cfg.kind(li) == program.LINEAR]
    by_bucket, paths, lows, began = {}, {}, [], time.monotonic()
    for bucket, prompt in prompts:
        padded = np.zeros(bucket, np.int32)
        padded[:len(prompt)] = prompt
        reads = [scan(lp, params["wte"], jnp.asarray(padded), jnp.int32(len(prompt)))
                 for lp in layers]
        by_bucket[bucket] = float(max(np.asarray(r[0]) for r in reads))
        paths[bucket] = program.prefill_recurrence_path(cfg, bucket)
        lows.extend(float(np.asarray(r[1])) for r in reads)
    return {"error": max(by_bucket.values()), "error_products_bf16": min(lows),
            "by_bucket": by_bucket, "paths": paths, "layers": len(layers),
            "seconds": time.monotonic() - began}


def _limits(deficits, early, late, request, state_error=None, sampled=None, handover=None,
            scan_error=None):
    """One set of tokens' deficits under the true reference (every checked position, in
    order) against the three greedy limits; `early` and `late` mark limit 1's and limit 3's
    positions, `request` says whose each position is. `state_error`: limit 4's reading of
    the program that picked the tokens (None: its recurrence is the reference's).
    `sampled`: limit 5's perturbed deficits of the sampled requests' positions (None: no
    sampled request was served in full). `handover`: limit 6's reading (None: not read).
    `scan_error`: limit 7's (None: its scan is the reference's)."""
    def within(mask, margin):
        picked = deficits[mask]
        return float((picked <= margin).mean()) if picked.size else None

    by_request = [within(early & (request == r), base.LOGIT_MARGIN)
                  for r in np.unique(request) if (early & (request == r)).sum() >= MIN_JUDGED]
    judged = min(by_request) if by_request else None
    share = float((deficits <= SHARE_MARGIN).mean())
    tail = within(late, LATE_MARGIN)
    drawn = None if sampled is None or not sampled.size \
        else float((sampled <= SAMPLED_MARGIN).mean())
    fails = [limit for limit, failed in (
        ("early", judged is not None and judged < MIN_JUDGED_WITHIN),
        ("all", share < base.MIN_SHARE_WITHIN),
        ("late", tail is not None and tail < MIN_LATE_WITHIN),
        ("state_step", state_error is not None
         and not state_error <= MAX_STATE_STEP_ERROR),
        ("sampled", drawn is not None and drawn < MIN_SAMPLED_WITHIN),
        ("handover", handover is not None and not handover <= MAX_HANDOVER_ERROR),
        ("scan", scan_error is not None and not scan_error <= MAX_SCAN_ERROR)) if failed]
    return {"positions": int(deficits.size), "judged": int(early.sum()),
            "left_out": int(deficits.size - early.sum()), "judged_within_margin": judged,
            "judged_within_by_request": by_request,
            "max_logit_deficit": float(deficits[early].max()) if early.any() else None,
            "share_within_margin": share, "late_judged": int(late.sum()),
            "late_within_margin": tail, "state_step_error": state_error,
            "sampled_positions": 0 if sampled is None else int(sampled.size),
            "sampled_within_margin": drawn,
            "sampled_max_deficit": float(sampled.max()) if drawn is not None else None,
            "handover_error": handover, "scan_error": scan_error,
            "fails": fails}


base.ModelServed = Qwen3NextServed


def run(ctx):
    run = base.run(ctx)
    stats = Qwen3NextServed.last_stats
    groups = {g["name"]: g for g in stats.get("groups") or []}
    counted = run["model1"]
    run["cache_groups"] = groups
    run["state"] = stats.get("state")
    if run.get("scopes") is not None:
        run["scopes_busy_s"] = _Scopes.busy_s
    decode_paths = stats.get("decode_attention")
    prefill = stats.get("prefill_attention") or {}
    facts = run["facts"]
    facts.update(
        cache_groups=groups, state=stats.get("state"), prefill_attention=prefill,
        prefix_cache=stats.get("prefix_cache"),
        experts_held=stats.get("experts_held"), vocab_slice=stats.get("vocab_slice"),
        **{name: counted.get(name) for name in (
            "moe_kernel_passes", "moe_rows_computed", "moe_picks_routed", "moe_picks_held",
            "decode_moe_picks_routed", "decode_moe_picks_held", "gdn_state_steps",
            "gdn_prefill_rows", "gdn_prefill_chunks", "decode_rows_full")})
    run["why_incorrect"] = [
        (f"of the {facts.get('judged')} served greedy positions among each checked request's "
         f"first {EARLY} that are clear of a tie in the picks by {base.PICK_GAP}, the least "
         f"share a request within {base.LOGIT_MARGIN} of the reference's best logit is "
         f"{facts.get('judged_within_margin')} (at least {MIN_JUDGED_WITHIN}); of all "
         f"{facts.get('positions')}, {facts.get('share_within_margin')} are within "
         f"{SHARE_MARGIN} (at least {base.MIN_SHARE_WITHIN}); of the {facts.get('late_judged')} "
         f"last of the longest answers, {facts.get('late_within_margin')} are "
         f"within {LATE_MARGIN} (at least {MIN_LATE_WITHIN}); a served state block one step "
         f"on is {facts.get('state_step_error')} from the float32 recurrence's (at most "
         f"{MAX_STATE_STEP_ERROR}); of the {facts.get('sampled_positions')} positions of "
         f"sampled requests, {facts.get('sampled_within_margin')} lie within {SAMPLED_MARGIN} "
         f"of the largest perturbed reference logit (at least {MIN_SAMPLED_WITHIN}); what a "
         f"prefill of the engine leaves behind at a prompt's last row is "
         f"{facts.get('handover_error')} from the reference's (at most {MAX_HANDOVER_ERROR}); "
         f"a prompt's chunked scan is {facts.get('scan_error')} from the recurrence token by "
         f"token (at most {MAX_SCAN_ERROR}): fails {facts.get('fails')}")
        if why.startswith("of ") and "served greedy positions" in why else why
        for why in run["why_incorrect"]]
    state = stats.get("state") or {}
    if not isinstance(decode_paths, dict) or "gather" in decode_paths.values():
        run["why_incorrect"].append(f"the decode step gathered: {decode_paths}")
        run["correct"] = False
    if state.get("recurrence_path") != "kernel" \
            or state.get("prefill_recurrence_path") != "kernel":
        run["why_incorrect"].append(f"a recurrence did not run as the kernel: {state}")
        run["correct"] = False
    if prefill.get("path") != "flash" or prefill.get("cold_gather"):
        run["why_incorrect"].append(f"a prefill gathered: {prefill}")
        run["correct"] = False
    return run
