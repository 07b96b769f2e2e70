-- TPC-DS v3.2 query 28 (query28.tpl), the parameters of its qualification run as Apache
-- Spark's tpcds/q28.sql carries them (written from memory, as q67.sql's month sequence was):
-- six quantity bands 0-5, 6-10, 11-15, 16-20, 21-25, 26-30 with (list price, coupon,
-- wholesale) = (8, 459, 57), (90, 2323, 31), (142, 12214, 79), (135, 6071, 38),
-- (122, 836, 17), (154, 7326, 7); ranges x and x + 10, x and x + 1000, x and x + 20.
-- Departures from the spec's text, none of which changes an answer:
--   the comma list of the six derived tables is written as CROSS JOIN (the parser has no
--   comma join); each derived table is one row, so the product is one row;
--   ss_list_price, ss_coupon_amt and ss_wholesale_cost, decimal(7,2), are doubles (the
--   configuration's `assumed`), so B*_LP is a double average and not a decimal(11,6);
--   "limit 100" is the template's [_LIMITC] in its LIMIT form.
select *
from (select avg(ss_list_price) B1_LP, count(ss_list_price) B1_CNT,
             count(distinct ss_list_price) B1_CNTD
      from store_sales
      where ss_quantity between 0 and 5
        and (ss_list_price between 8 and 8 + 10
             or ss_coupon_amt between 459 and 459 + 1000
             or ss_wholesale_cost between 57 and 57 + 20)) B1
     cross join
     (select avg(ss_list_price) B2_LP, count(ss_list_price) B2_CNT,
             count(distinct ss_list_price) B2_CNTD
      from store_sales
      where ss_quantity between 6 and 10
        and (ss_list_price between 90 and 90 + 10
             or ss_coupon_amt between 2323 and 2323 + 1000
             or ss_wholesale_cost between 31 and 31 + 20)) B2
     cross join
     (select avg(ss_list_price) B3_LP, count(ss_list_price) B3_CNT,
             count(distinct ss_list_price) B3_CNTD
      from store_sales
      where ss_quantity between 11 and 15
        and (ss_list_price between 142 and 142 + 10
             or ss_coupon_amt between 12214 and 12214 + 1000
             or ss_wholesale_cost between 79 and 79 + 20)) B3
     cross join
     (select avg(ss_list_price) B4_LP, count(ss_list_price) B4_CNT,
             count(distinct ss_list_price) B4_CNTD
      from store_sales
      where ss_quantity between 16 and 20
        and (ss_list_price between 135 and 135 + 10
             or ss_coupon_amt between 6071 and 6071 + 1000
             or ss_wholesale_cost between 38 and 38 + 20)) B4
     cross join
     (select avg(ss_list_price) B5_LP, count(ss_list_price) B5_CNT,
             count(distinct ss_list_price) B5_CNTD
      from store_sales
      where ss_quantity between 21 and 25
        and (ss_list_price between 122 and 122 + 10
             or ss_coupon_amt between 836 and 836 + 1000
             or ss_wholesale_cost between 17 and 17 + 20)) B5
     cross join
     (select avg(ss_list_price) B6_LP, count(ss_list_price) B6_CNT,
             count(distinct ss_list_price) B6_CNTD
      from store_sales
      where ss_quantity between 26 and 30
        and (ss_list_price between 154 and 154 + 10
             or ss_coupon_amt between 7326 and 7326 + 1000
             or ss_wholesale_cost between 7 and 7 + 20)) B6
limit 100
