"""The traffic generator: the same lengths for every seed, the tokens
drawn from it."""
import loadgen


def test_sessions_are_the_listed_contexts():
    mix = loadgen.load_mix("long_decode")
    items = loadgen.generate(mix, 3, 49155)
    assert [len(i.prompt) for i in items] == mix["contexts"]
    assert all(i.max_new == mix["max_new_tokens"] for i in items)
    assert loadgen.max_context(mix) == max(mix["contexts"]) + mix["max_new_tokens"]


def test_same_seed_same_requests_other_seed_other_tokens():
    mix = loadgen.load_mix("long_decode")
    x = loadgen.generate(mix, 2 ** 31 + 7, 49155)
    y = loadgen.generate(mix, 2 ** 31 + 7, 49155)
    z = loadgen.generate(mix, 8, 49155)
    assert [(i.prompt, i.max_new) for i in x] == [(i.prompt, i.max_new) for i in y]
    assert [len(i.prompt) for i in x] == [len(i.prompt) for i in z]
    assert [i.prompt for i in x] != [i.prompt for i in z]
    assert all(0 <= t < 49155 for i in x for t in i.prompt)
