"""WatDiv data in the shape of the suite's ``wsdbm`` model (Aluç, Hartig, Özsu,
Daudjee, ISWC 2014, section 4 and the data generator's model file), from
``--seed``.

Entity classes: the scalable ones hold ``count x scale factor`` instances
(User 1,000, Product 250, Retailer 12, Offer 900, Review 1,500, Purchase
1,500, Website 50), the others a fixed number (City 240, Country 25, Topic
250, SubGenre 145, Genre 21, Language 25, AgeGroup 9, Gender 2, Role 3,
ProductCategory 15).  86 predicates under ten namespaces.  A literal predicate
is carried by a share of its class's instances (the model's ``pgroup``
probabilities; predicates of one group come together); an association draws
its objects uniformly, by a Zipfian weight over the object's index, or with
a normally distributed number of objects a subject, as ``ASSOCIATIONS`` and
the code below say.  The skew the suite is about lives in ``gr:offers`` (a
retailer's offers), ``og:tag`` (a topic's products), ``wsdbm:hasGenre`` (a
sub-genre's products), ``wsdbm:likes``, ``rev:hasReview`` and
``wsdbm:purchaseFor`` (a product's fans, reviews and purchases),
``wsdbm:follows`` (a user's followers), ``wsdbm:subscribes`` (a website's
subscribers) and ``wsdbm:makesPurchase`` (a user's purchases): instance 0 of
the object class is the hottest, as a Zipfian rank has it.

Not the C++ generator's stream of random numbers and not its model file's
numbers where the configuration's ``assumed`` says so: the same shape and
about the same size (about 109,000 triples a scale factor, ``wsdbm:friendOf``
the largest predicate), not the same file.  Every literal is plain.
Vectorised: scale factor 100 (10.9 M triples, 0.99 M terms) takes under ten
seconds.
"""

import numpy as np

NAMESPACES = {
    "wsdbm": "http://db.uwaterloo.ca/~galuc/wsdbm/",
    "sorg": "http://schema.org/",
    "gr": "http://purl.org/goodrelations/",
    "og": "http://ogp.me/ns#",
    "rev": "http://purl.org/stuff/rev#",
    "foaf": "http://xmlns.com/foaf/",
    "dc": "http://purl.org/dc/terms/",
    "mo": "http://purl.org/ontology/mo/",
    "gn": "http://www.geonames.org/ontology#",
    "rdf": "http://www.w3.org/1999/02/22-rdf-syntax-ns#",
}
SCALABLE = {"User": 1000, "Product": 250, "Retailer": 12, "Offer": 900,
            "Review": 1500, "Purchase": 1500, "Website": 50}
FIXED = {"City": 240, "Country": 25, "Topic": 250, "SubGenre": 145, "Genre": 21,
         "Language": 25, "AgeGroup": 9, "Gender": 2, "Role": 3,
         "ProductCategory": 15}
PREDICATES = (
    "dc:Location",
    "foaf:age", "foaf:familyName", "foaf:givenName", "foaf:homepage",
    "gn:parentCountry",
    "gr:description", "gr:includes", "gr:name", "gr:offers", "gr:price",
    "gr:serialNumber", "gr:validFrom", "gr:validThrough",
    "mo:artist", "mo:conductor", "mo:movement", "mo:opus", "mo:performed_in",
    "mo:performer", "mo:producer", "mo:record_number", "mo:release",
    "og:tag", "og:title",
    "rdf:type",
    "rev:hasReview", "rev:rating", "rev:reviewer", "rev:text", "rev:title",
    "rev:totalVotes",
    "sorg:actor", "sorg:aggregateRating", "sorg:author", "sorg:award",
    "sorg:birthDate", "sorg:bookEdition", "sorg:caption", "sorg:contactPoint",
    "sorg:contentRating", "sorg:contentSize", "sorg:datePublished",
    "sorg:description", "sorg:director", "sorg:duration", "sorg:editor",
    "sorg:eligibleQuantity", "sorg:eligibleRegion", "sorg:email",
    "sorg:employee", "sorg:expires", "sorg:faxNumber", "sorg:isbn",
    "sorg:jobTitle", "sorg:keywords", "sorg:language", "sorg:legalName",
    "sorg:name", "sorg:nationality", "sorg:numberOfPages", "sorg:openingHours",
    "sorg:paymentAccepted", "sorg:priceValidUntil", "sorg:printColumn",
    "sorg:printEdition", "sorg:printPage", "sorg:printSection", "sorg:producer",
    "sorg:publisher", "sorg:telephone", "sorg:text", "sorg:trailer", "sorg:url",
    "sorg:wordCount",
    "wsdbm:follows", "wsdbm:friendOf", "wsdbm:gender", "wsdbm:hasGenre",
    "wsdbm:hits", "wsdbm:likes", "wsdbm:makesPurchase", "wsdbm:purchaseDate",
    "wsdbm:purchaseFor", "wsdbm:subscribes", "wsdbm:userId")

# Literal predicates: class -> [(share of instances, [(predicate, kind)])];
# the predicates of one entry come together (the model's pgroup).  A kind is
# ("serial",): the instance's number, ("int", lo, hi), ("date",), ("name",) or
# ("words", lo, hi): that many words.
LITERALS = {
    "User": [
        (1.0, [("wsdbm:userId", ("serial",))]),
        (0.8, [("foaf:givenName", ("name",)), ("foaf:familyName", ("name",))]),
        (0.7, [("sorg:email", ("words", 1, 1))]),
        (0.5, [("sorg:birthDate", ("date",))]),
        (0.2, [("sorg:telephone", ("int", 1_000_000, 9_999_999))]),
        (0.05, [("sorg:jobTitle", ("words", 1, 3))]),
    ],
    "Product": [
        (1.0, [("og:title", ("words", 2, 5))]),
        (0.6, [("sorg:caption", ("words", 5, 15))]),
        (0.7, [("sorg:description", ("words", 20, 100))]),
        (0.6, [("sorg:keywords", ("words", 3, 10))]),
        (0.5, [("sorg:text", ("words", 20, 140))]),
        (0.6, [("sorg:contentRating", ("int", 1, 18)),
               ("sorg:contentSize", ("int", 1, 5000))]),
        (0.5, [("sorg:publisher", ("name",))]),
        (0.3, [("sorg:datePublished", ("date",))]),
        (0.2, [("sorg:aggregateRating", ("int", 1, 10))]),
        (0.1, [("sorg:award", ("words", 2, 4))]),
    ],
    "Offer": [
        (1.0, [("gr:price", ("int", 1, 2000)), ("gr:serialNumber", ("serial",))]),
        (0.9, [("gr:validFrom", ("date",)), ("gr:validThrough", ("date",))]),
        (0.9, [("sorg:eligibleQuantity", ("int", 1, 100))]),
        (0.9, [("sorg:priceValidUntil", ("date",))]),
    ],
    "Retailer": [
        (1.0, [("gr:name", ("words", 1, 3))]),
        (0.7, [("gr:description", ("words", 10, 40))]),
        (0.6, [("sorg:legalName", ("words", 2, 4))]),
        (0.5, [("sorg:openingHours", ("words", 2, 4)),
               ("sorg:paymentAccepted", ("words", 1, 3))]),
        (0.5, [("sorg:telephone", ("int", 1_000_000, 9_999_999))]),
        (0.3, [("sorg:faxNumber", ("int", 1_000_000, 9_999_999))]),
        (0.5, [("sorg:email", ("words", 1, 1))]),
    ],
    "Review": [
        (1.0, [("rev:rating", ("int", 1, 10))]),
        (0.8, [("rev:title", ("words", 2, 6))]),
        (0.7, [("rev:text", ("words", 20, 140))]),
        (0.5, [("rev:totalVotes", ("int", 0, 500))]),
    ],
    "Purchase": [
        (1.0, [("wsdbm:purchaseDate", ("date",))]),
    ],
    "Website": [
        (1.0, [("sorg:url", ("words", 1, 1)), ("wsdbm:hits", ("int", 0, 1_000_000))]),
        (0.8, [("sorg:name", ("words", 1, 3))]),
    ],
}
# Literal predicates a product carries by its category: five kinds of three
# categories each (video, music, books, print, the rest)
CATEGORY_LITERALS = {
    (0, 1, 2): [
        (0.6, [("sorg:trailer", ("words", 1, 1))]),
        (0.7, [("sorg:duration", ("int", 1, 300))]),
    ],
    (3, 4, 5): [
        (0.5, [("mo:movement", ("words", 1, 2)), ("mo:opus", ("int", 1, 200))]),
        (0.6, [("mo:record_number", ("int", 1, 100_000)), ("mo:release", ("date",))]),
        (0.3, [("mo:performed_in", ("words", 1, 2))]),
    ],
    (6, 7, 8): [
        (0.8, [("sorg:isbn", ("int", 100_000_000, 999_999_999))]),
        (0.6, [("sorg:bookEdition", ("int", 1, 12)),
               ("sorg:numberOfPages", ("int", 20, 1500))]),
    ],
    (9, 10, 11): [
        (0.7, [("sorg:printColumn", ("int", 1, 8)), ("sorg:printEdition", ("int", 1, 5)),
               ("sorg:printPage", ("int", 1, 64)), ("sorg:printSection", ("words", 1, 1))]),
        (0.6, [("sorg:wordCount", ("int", 50, 5000))]),
        (0.3, [("sorg:expires", ("date",))]),
    ],
}
# Product -> User by category: (categories, predicate, share of the products)
CATEGORY_PEOPLE = (
    ((0, 1, 2), "sorg:actor", 0.8), ((0, 1, 2), "sorg:director", 0.7),
    ((0, 1, 2), "sorg:producer", 0.4),
    ((3, 4, 5), "mo:artist", 0.8), ((3, 4, 5), "mo:conductor", 0.5),
    ((3, 4, 5), "mo:performer", 0.5), ((3, 4, 5), "mo:producer", 0.3),
    ((6, 7, 8), "sorg:author", 0.9), ((6, 7, 8), "sorg:editor", 0.4),
)
# Associations: predicate -> (subject class, object class, share of subjects
# that carry it, mean and deviation of the objects a carrier has (normal,
# rounded, at least ``least``), exponent of the Zipfian weight over the
# object's index (0: uniform))
ASSOCIATIONS = {
    "wsdbm:friendOf": ("User", "User", 0.4, (110.0, 25.0, 1), 0.0),
    "wsdbm:follows": ("User", "User", 0.5, (55.0, 20.0, 1), 0.6),
    "wsdbm:likes": ("User", "Product", 1.0, (3.0, 1.5, 1), 0.7),
    "wsdbm:subscribes": ("User", "Website", 0.4, (3.0, 1.5, 1), 0.8),
    "dc:Location": ("User", "City", 0.8, (1.0, 0.0, 1), 0.0),
    "sorg:nationality": ("User", "Country", 0.8, (1.0, 0.0, 1), 0.0),
    "foaf:age": ("User", "AgeGroup", 0.8, (1.0, 0.0, 1), 0.0),
    "wsdbm:gender": ("User", "Gender", 0.8, (1.0, 0.0, 1), 0.0),
    "rdf:type.User": ("User", "Role", 1.0, (1.0, 0.0, 1), 0.0),
    "foaf:homepage.User": ("User", "Website", 0.05, (1.0, 0.0, 1), 0.0),
    "og:tag": ("Product", "Topic", 1.0, (6.0, 2.0, 1), 1.0),
    "wsdbm:hasGenre": ("Product", "SubGenre", 1.0, (2.5, 1.0, 1), 1.0),
    "sorg:language": ("Product", "Language", 0.5, (1.0, 0.0, 1), 1.0),
    "foaf:homepage": ("Product", "Website", 0.5, (1.0, 0.0, 1), 0.0),
    "gr:includes": ("Offer", "Product", 1.0, (1.0, 0.0, 1), 0.0),
    "sorg:eligibleRegion": ("Offer", "Country", 0.9, (1.0, 0.0, 1), 0.0),
    "rev:reviewer": ("Review", "User", 1.0, (1.0, 0.0, 1), 0.0),
    "wsdbm:purchaseFor": ("Purchase", "Product", 1.0, (1.0, 0.0, 1), 0.7),
    "sorg:language.Website": ("Website", "Language", 0.9, (1.0, 0.0, 1), 1.0),
    "gn:parentCountry": ("City", "Country", 1.0, (1.0, 0.0, 1), 0.0),
    "og:tag.SubGenre": ("SubGenre", "Topic", 1.0, (8.0, 2.0, 4), 0.0),
    "rdf:type.SubGenre": ("SubGenre", "Genre", 1.0, (1.0, 0.0, 1), 0.0),
    "sorg:contactPoint": ("Retailer", "User", 0.5, (1.0, 0.0, 1), 0.0),
    "sorg:employee": ("Retailer", "User", 0.8, (3.0, 1.5, 1), 0.0),
}
# Each instance of the subject class belongs to one instance of the object
# class, drawn by a Zipfian weight: predicate -> (owner, owned, exponent).
# The triple runs from the owner: <retailer> gr:offers <offer>.
OWNERSHIPS = {
    "gr:offers": ("Retailer", "Offer", 1.0),
    "rev:hasReview": ("Product", "Review", 0.7),
    "wsdbm:makesPurchase": ("User", "Purchase", 0.5),
}
_SYLLABLES = ("ba be bi bo bu da de di do du fa fe fi fo fu ga ge gi go gu "
              "ka ke ki ko ku la le li lo lu ma me mi mo mu na ne ni no nu "
              "pa pe pi po pu ra re ri ro ru sa se si so su ta te ti to tu "
              "va ve vi vo").split()
VOCABULARY = [a + b + c for a in _SYLLABLES[:16] for b in _SYLLABLES for c in ("", "n", "r", "s")]
NAMES = [(a + b).capitalize() for a in _SYLLABLES for b in _SYLLABLES[:32]]


def iri(prefixed: str) -> str:
    pre, _, local = prefixed.partition(":")
    return NAMESPACES[pre] + local


def _zipf_draw(rng, n_objects: int, size: int, exponent: float) -> np.ndarray:
    if not exponent:
        return rng.integers(0, n_objects, size)
    cdf = np.cumsum(np.arange(1, n_objects + 1, dtype=np.float64) ** -exponent)
    return np.searchsorted(cdf, rng.random(size) * cdf[-1], side="right").clip(
        0, n_objects - 1)


class _Builder:
    """Term table by blocks and triple blocks of ids."""

    def __init__(self, rng):
        self.rng = rng
        self.terms, self.blocks = [], []
        self.first = {}  # class -> id of its instance 0
        self.count = {}
        self.pred = {}
        self._pools = {}
        self._voc = np.array(VOCABULARY, dtype=object)

    def add_terms(self, texts) -> np.ndarray:
        start = len(self.terms)
        self.terms.extend(texts)
        return np.arange(start, len(self.terms), dtype=np.int64)

    def add_class(self, name: str, n: int) -> None:
        self.first[name], self.count[name] = len(self.terms), n
        base = NAMESPACES["wsdbm"] + name
        self.terms.extend([f"<{base}{i}>" for i in range(n)])

    def ids(self, cls: str, index) -> np.ndarray:
        return self.first[cls] + np.asarray(index, dtype=np.int64)

    def add(self, s, predicate: str, o) -> None:
        s = np.asarray(s, dtype=np.int64)
        o = np.asarray(o, dtype=np.int64)
        # "og:tag.SubGenre" is og:tag from another subject class
        p = self.pred[predicate.partition(".")[0]]
        self.blocks.append((s, np.full(len(s), p, np.int64), o))

    def pool(self, kind: tuple, values) -> np.ndarray:
        """The ids of a kind's shared literals, made on first use."""
        if kind not in self._pools:
            self._pools[kind] = self.add_terms([f'"{v}"' for v in values()])
        return self._pools[kind]

    # ---- literals, one id a triple unless pooled
    def literal_ids(self, kind: tuple, n: int) -> np.ndarray:
        rng = self.rng
        if kind[0] == "serial":
            return self.add_terms([f'"{i}"' for i in range(n)])
        if kind[0] == "int":
            lo, hi = kind[1], kind[2]
            values = rng.integers(lo, hi + 1, n)
            if hi - lo > 100_000:
                uniq, inverse = np.unique(values, return_inverse=True)
                return self.add_terms([f'"{v}"' for v in uniq.tolist()])[inverse]
            return self.pool(kind, lambda: range(lo, hi + 1))[values - lo]
        if kind[0] == "date":
            pool = self.pool(kind, lambda: (
                f"{y}-{m:02d}-{d:02d}" for y in range(1990, 2015)
                for m in range(1, 13) for d in range(1, 29)))
            return pool[rng.integers(0, len(pool), n)]
        if kind[0] == "name":
            pool = self.pool(kind, lambda: NAMES)
            return pool[rng.integers(0, len(pool), n)]
        lo, hi = kind[1], kind[2]
        lengths = rng.integers(lo, hi + 1, n)
        words = self._voc[rng.integers(0, len(self._voc), int(lengths.sum()))].tolist()
        ends = np.cumsum(lengths).tolist()
        starts = [0] + ends[:-1]
        return self.add_terms(
            ['"' + " ".join(words[a:b]) + '"' for a, b in zip(starts, ends)])

    def literals(self, subjects: np.ndarray, groups) -> None:
        for share, members in groups:
            who = subjects if share >= 1.0 else subjects[
                self.rng.random(len(subjects)) < share]
            for predicate, kind in members:
                self.add(who, predicate, self.literal_ids(kind, len(who)))

    def associate(self, predicate, subjects, obj_cls, share, fanout, exponent):
        rng = self.rng
        who = subjects if share >= 1.0 else subjects[rng.random(len(subjects)) < share]
        mean, dev, least = fanout
        if dev:
            n_obj = np.maximum(np.rint(rng.normal(mean, dev, len(who))), least).astype(np.int64)
        else:
            n_obj = np.full(len(who), int(mean), np.int64)
        n_obj = np.minimum(n_obj, self.count[obj_cls])
        s = np.repeat(who, n_obj)
        o = self.ids(obj_cls, _zipf_draw(rng, self.count[obj_cls], len(s), exponent))
        if n_obj.max(initial=1) > 1:
            # a subject names an object once
            pair = np.unique(s * np.int64(1 << 32) + o)
            s, o = pair >> 32, pair & np.int64((1 << 32) - 1)
        self.add(s, predicate, o)


def generate(config: dict, seed: int, scale=None) -> dict:
    """``{"terms", "s", "p", "o", "domains"}``: N-Triples terms, id columns
    and the constants a traffic file may draw (every instance of a class)."""
    sf = int(scale or config["scale_factor"])
    rng = np.random.default_rng([int(seed), 2014])
    b = _Builder(rng)
    b.pred = dict(zip(PREDICATES, b.add_terms([f"<{iri(p)}>" for p in PREDICATES])))
    for name, n in SCALABLE.items():
        b.add_class(name, n * sf)
    for name, n in FIXED.items():
        b.add_class(name, n)

    def all_of(cls):
        return b.ids(cls, np.arange(b.count[cls]))

    for cls, groups in LITERALS.items():
        b.literals(all_of(cls), groups)
    # a product is of one category, drawn with a mild Zipfian weight
    products = all_of("Product")
    category = _zipf_draw(rng, FIXED["ProductCategory"], len(products), 0.3)
    b.add(products, "rdf:type", b.ids("ProductCategory", category))
    for categories, groups in CATEGORY_LITERALS.items():
        b.literals(products[np.isin(category, categories)], groups)
    for categories, predicate, share in CATEGORY_PEOPLE:
        b.associate(predicate, products[np.isin(category, categories)], "User",
                    share, (1.0, 0.0, 1), 0.0)
    for predicate, (subj, obj, share, fanout, exponent) in ASSOCIATIONS.items():
        b.associate(predicate, all_of(subj), obj, share, fanout, exponent)
    for predicate, (owner, owned, exponent) in OWNERSHIPS.items():
        things = all_of(owned)
        b.add(b.ids(owner, _zipf_draw(rng, b.count[owner], len(things), exponent)),
              predicate, things)

    s = np.concatenate([blk[0] for blk in b.blocks])
    p = np.concatenate([blk[1] for blk in b.blocks])
    o = np.concatenate([blk[2] for blk in b.blocks])
    # a friend is not oneself, and nobody follows themself
    keep = ~((s == o) & np.isin(p, [b.pred["wsdbm:friendOf"], b.pred["wsdbm:follows"]]))
    # one id a distinct term: two literals of one text become one term
    index = {t: i for i, t in enumerate(dict.fromkeys(b.terms))}
    if len(index) < len(b.terms):
        remap = np.fromiter((index[t] for t in b.terms), np.int64, len(b.terms))
        s, p, o = remap[s], remap[p], remap[o]
    wsdbm = NAMESPACES["wsdbm"]
    domains = {
        domain: [f"{wsdbm}{cls}{i}" for i in range(b.count[cls])]
        for domain, cls in (
            ("retailer", "Retailer"), ("country", "Country"),
            ("category", "ProductCategory"), ("agegroup", "AgeGroup"),
            ("subgenre", "SubGenre"), ("user", "User"), ("topic", "Topic"),
            ("website", "Website"), ("city", "City"))}
    return {"terms": list(index), "s": s[keep], "p": p[keep], "o": o[keep],
            "domains": domains}
