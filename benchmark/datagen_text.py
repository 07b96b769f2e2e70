"""datagen's tables with the text columns as TPC-H defines them.

`generate(sf, seed)` is `datagen.generate(sf, seed)` with c_address,
c_comment, o_comment and l_comment replaced: where datagen draws a text
column from a pool of 8,192 phrases, so that it uploads as a dictionary of
a few thousand entries, the specification (clause 4.2.2.10 to 4.2.2.14)
makes a comment a substring of a long pseudo-text at a random offset and
length (`text string[min, max]`), and an address a random string
(`v-string[min, max]`): nearly every row its own value.

The pseudo-text is sentences of dbgen's grammar (noun and verb phrases, a
prepositional phrase, a terminator) over its word classes as far as this
writer knows them; `special` is an adjective and `requests` a noun like any
other word and nothing is planted, so Q13's `%special%requests%` matches
what falls out (the share is in the configuration's `assumed`). POOL_BYTES
is 64 MiB against dbgen's 300 MB: a value is still one of some 4e9
(offset, length) pairs. Everything is made in bulk with numpy from --seed;
columns are Arrow `string` (32-bit offsets: l_comment at SF5 is 0.8 GB).

Imports nothing of the engine.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pyarrow as pa

import datagen
from datagen import write_parquet  # noqa: F401 - a generator module's API

POOL_BYTES = 1 << 26
TEXT_BLOCKS = 64
ROWS_A_TASK = 1 << 20

NOUNS = ("foxes ideas theodolites pinto_beans instructions dependencies "
         "excuses platelets asymptotes courts dolphins multipliers sauternes "
         "warthogs frets dinos attainments somas Tiresias' patterns forges "
         "braids hockey_players frays warhorses dugouts notornis epitaphs "
         "pearls tithes waters orbits gifts sheaves depths sentiments decoys "
         "realms pains grouches escapades packages requests accounts "
         "deposits").split()
VERBS = ("sleep wake are cajole haggle nag use boost affix detect integrate "
         "maintain nod was lose sublate solve thrash promise engage hinder "
         "print x-ray breach eat grow impress mold poach serve run dazzle "
         "snooze doze unwind kindle play hang believe doubt").split()
ADJECTIVES = ("furious sly careful blithe quick fluffy slow quiet ruthless "
              "thin close dogged daring brave stealthy permanent enticing "
              "idle busy regular final ironic even bold silent special "
              "pending unusual express").split()
ADVERBS = ("sometimes always never furiously slyly carefully blithely "
           "quickly fluffily slowly quietly ruthlessly thinly closely "
           "doggedly daringly bravely stealthily permanently enticingly "
           "idly busily regularly finally ironically evenly boldly "
           "silently").split()
PREPOSITIONS = ("about above according_to across after against along "
                "alongside_of among around at atop before behind beneath "
                "beside besides between beyond by despite during except for "
                "from in_place_of inside instead_of into near of on outside "
                "over past since through throughout to toward under until "
                "up upon without with within").split()
AUXILIARIES = ("do may might shall will would can could should ought_to "
               "must will_have_to shall_have_to could_have_to "
               "should_have_to must_have_to need_to try_to").split()
TERMINATORS = [".", ";", ":", "?", "!", "--"]
CLASSES = {"n": NOUNS, "v": VERBS, "j": ADJECTIVES, "d": ADVERBS,
           "p": PREPOSITIONS, "x": AUXILIARIES, "t": TERMINATORS}
#: sentences: noun phrase, verb phrase, at times a prepositional phrase or
#: an object, a terminator (dbgen's grammar, its weights not kept)
SENTENCES = ["jnvt", "jnxvdt", "njnvpnt", "djnvdt", "jnvjnt", "nxvpjnt",
             "jjnvpjnt", "nvdt", "djnxvpdjnt", "jnpjnvt"]
ADDRESS_CHARS = np.frombuffer(
    b"0123456789abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ ,",
    np.uint8)

#: the text columns: (table, column, shortest, longest); a comment is a
#: substring of the pseudo-text, an address a random string
TEXT_COLUMNS = [("customer", "c_address", 10, 40),
                ("customer", "c_comment", 29, 116),
                ("orders", "o_comment", 19, 78),
                ("lineitem", "l_comment", 10, 43)]


def _text_block(rng, nbytes: int) -> np.ndarray:
    """uint8[nbytes] of grammar sentences, words a space apart."""
    words, first = [], {}
    for cls, ws in CLASSES.items():
        first[cls] = len(words)
        words += [w.replace("_", " ") for w in ws]
    word_bytes = [(w + " ").encode() for w in words]
    w_len = np.array([len(b) for b in word_bytes], np.int32)
    w_start = np.concatenate([[0], np.cumsum(w_len)[:-1]]).astype(np.int32)
    flat = np.frombuffer(b"".join(word_bytes), np.uint8)
    longest = max(len(f) for f in SENTENCES)
    n_sent = nbytes // 24 + 16          # a sentence is 25 bytes or more
    form = rng.integers(0, len(SENTENCES), n_sent)
    tokens = np.full((n_sent, longest), -1, np.int32)
    for f, letters in enumerate(SENTENCES):
        rows = np.flatnonzero(form == f)
        for slot, cls in enumerate(letters):
            tokens[rows, slot] = first[cls] + rng.integers(
                0, len(CLASSES[cls]), len(rows))
    tokens = tokens[tokens >= 0]
    lens = w_len[tokens]
    starts = np.zeros(len(lens), np.int32)
    np.cumsum(lens[:-1], out=starts[1:])
    src = np.repeat(w_start[tokens] - starts, lens) \
        + np.arange(int(starts[-1]) + int(lens[-1]), dtype=np.int32)
    text = flat[src]
    # a terminator follows its word with no space between
    keep = np.ones(len(text), np.bool_)
    keep[starts[tokens >= first["t"]] - 1] = False
    text = text[keep]
    assert len(text) >= nbytes, (len(text), nbytes)
    return text[:nbytes]


def pseudo_text(seed_seq, nbytes: int) -> np.ndarray:
    """The pseudo-text: TEXT_BLOCKS blocks of sentences, each from its own
    stream, made in threads and laid end to end (the same text whatever
    the number of cores)."""
    kids = seed_seq.spawn(TEXT_BLOCKS)
    size = -(-nbytes // TEXT_BLOCKS)
    with ThreadPoolExecutor(min(TEXT_BLOCKS, os.cpu_count() or 1,
                                16)) as ex:
        blocks = list(ex.map(lambda k: _text_block(
            np.random.default_rng(k), size), kids))
    return np.ascontiguousarray(np.concatenate(blocks)[:nbytes])


def _substrings(pool: np.ndarray, seeds, n: int, lo: int, hi: int
                ) -> pa.Array:
    """n strings, each pool[offset : offset + length] with both drawn
    from the row's task's stream; tasks of ROWS_A_TASK rows in threads."""
    bounds = list(range(0, n, ROWS_A_TASK)) + [n]
    lengths = np.empty(n, np.int32)
    for t, (a, b) in enumerate(zip(bounds[:-1], bounds[1:])):
        lengths[a:b] = np.random.default_rng(seeds[2 * t]).integers(
            lo, hi + 1, b - a)
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum(lengths, out=offsets[1:])
    assert offsets[-1] < 2**31, "a string column holds under 2 GiB"
    data = np.empty(int(offsets[-1]), np.uint8)

    windows = np.lib.stride_tricks.sliding_window_view(pool, hi)

    def fill(t):
        a, b = bounds[t], bounds[t + 1]
        at = np.random.default_rng(seeds[2 * t + 1]).integers(
            0, len(windows), b - a)
        rows = windows[at]          # each row's longest form, then cut
        data[offsets[a]:offsets[b]] = rows[
            np.arange(hi, dtype=np.int32) < lengths[a:b, None]]

    with ThreadPoolExecutor(min(len(bounds) - 1, os.cpu_count() or 1,
                                16)) as ex:
        list(ex.map(fill, range(len(bounds) - 1)))
    return pa.StringArray.from_buffers(
        n, pa.py_buffer(offsets.astype(np.int32)), pa.py_buffer(data))


def generate(sf: float, seed: int) -> dict:
    """datagen's three tables at scale `sf`, their four text columns at
    the spec's cardinality (module docstring)."""
    tables = datagen.generate(sf, seed)
    root = np.random.SeedSequence([int(seed) % (1 << 63), 0x7e87])
    kids = root.spawn(1 + len(TEXT_COLUMNS))
    text = pseudo_text(kids[0], POOL_BYTES)
    for (table, column, lo, hi), kid in zip(TEXT_COLUMNS, kids[1:]):
        tb = tables[table]
        n = tb.num_rows
        pool = text
        if column == "c_address":
            pool = ADDRESS_CHARS[np.random.default_rng(kid).integers(
                0, len(ADDRESS_CHARS), max(n * hi, hi + 1))]
        seeds = kid.spawn(2 * (n // ROWS_A_TASK + 1))
        tables[table] = tb.set_column(
            tb.schema.get_field_index(column), column,
            _substrings(pool, seeds, n, lo, hi))
    return tables
